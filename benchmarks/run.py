"""Benchmark orchestrator: one module per paper table/figure.

Usage:  PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig7,...]
        PYTHONPATH=src python -m benchmarks.run --smoke
Prints ``name,metric,...`` CSV rows per benchmark plus a paper-claim
validation summary (EXPERIMENTS.md records the full history).

``--smoke`` is the CI gate for the perf entry points: tiny N, no plots,
exits nonzero if recall collapses or batching stops paying.
"""
import argparse
import importlib
import sys
import time
import traceback

MODULES = [
    ("fig7_recall_qps", "Fig 7: LCPS recall-QPS curves"),
    ("fig8_hcps", "Fig 8: HCPS recall-QPS curves"),
    ("table3_dist_comps", "Table 3: distance comps @0.8 recall"),
    ("fig9_selectivity", "Fig 9: selectivity sweep + router"),
    ("fig10_correlation", "Fig 10: query-correlation robustness"),
    ("fig11_scaling", "Fig 11: dataset-size scaling"),
    ("table45_tti_size", "Tables 4+5: TTI and index size"),
    ("fig12_pruning", "Fig 12: pruning ablation"),
    ("fig13_graph_quality", "Fig 13: predicate-subgraph quality"),
    ("bench_batched_search", "Batched search: jit buckets x kernel QPS"),
    ("bench_sharded_search", "Sharded search: device-count x batch QPS"),
    ("bench_corpus_sharded", "Corpus-sharded SPMD: mesh-shape x batch QPS"),
    ("bench_serving_runtime",
     "Serving runtime: Poisson open loop vs closed loop"),
    ("bench_neighbor_expand", "Neighbor expansion: strategy x cap x impl"),
    ("bench_predicate_compile",
     "Predicate programs: host mask path vs compiled on-device"),
]


def smoke() -> int:
    """Tiny-N gate over the batched-search pipeline (CI: ~a minute)."""
    from benchmarks import bench_batched_search
    rows, checks = bench_batched_search.run(quick=True, write_json=False)
    for r in rows:
        print(",".join(str(x) for x in r))
    ok = True
    for name, passed in checks.items():
        print(f"  [smoke] {name}: {'PASS' if passed else 'FAIL'}")
        ok &= bool(passed)
    return 0 if ok else 1


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-N CI gate; nonzero exit on recall collapse")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    from repro.compile_cache import configure_compile_cache
    configure_compile_cache()
    if args.smoke:
        sys.exit(smoke())
    only = set(args.only.split(",")) if args.only else None

    all_checks, failures = {}, []
    for mod_name, title in MODULES:
        if only and mod_name not in only:
            continue
        print(f"\n=== {title} ({mod_name}) ===", flush=True)
        t0 = time.perf_counter()
        try:
            mod = importlib.import_module(f"benchmarks.{mod_name}")
            rows, checks = mod.run(quick=args.quick)
            for r in rows:
                print(",".join(str(x) for x in r))
            for k, v in checks.items():
                mark = "PASS" if v else "FAIL"
                print(f"  [claim] {k}: {mark}")
                all_checks[f"{mod_name}:{k}"] = v
            print(f"  ({time.perf_counter() - t0:.0f}s)")
        except Exception as e:
            traceback.print_exc()
            failures.append(mod_name)

    print("\n=== paper-claim validation summary ===")
    npass = sum(all_checks.values())
    for k, v in all_checks.items():
        print(f"{'PASS' if v else 'FAIL'}  {k}")
    print(f"\n{npass}/{len(all_checks)} claims validated; "
          f"{len(failures)} benchmark errors {failures or ''}")


if __name__ == "__main__":
    main()
