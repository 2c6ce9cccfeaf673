"""Corpus-sharded SPMD serving throughput: mesh-shape x batch-size sweep.

Measures serving QPS of the mesh-native corpus-sharded path
(``repro.distributed.corpus_parallel`` via ``ServingEngine.search_batch``)
against the retained host-loop oracle (``search_batch_host``) across
``(data, corpus)`` mesh shapes {1x8, 2x4, 4x2} x batch sizes {64, 256},
and writes ``BENCH_corpus_sharded.json`` at the repo root.  XLA fixes the
host device count at first init, so the sweep runs in ONE child process
launched with ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` —
every mesh shape is a reshape of the same 8 virtual devices (exactly the
"scaling the corpus is a mesh-shape change" claim).  That child is a CPU
rehearsal only: on a TPU host the sweep stops with an error
(``benchmarks.common.virtual_cpu_env``).

Claims validated:
  * the SPMD path is bit-identical to the host loop at every mesh shape
    and batch size (ids digests compared in-child);
  * recall does not collapse under corpus sharding;
  * trace economy: a steady-state engine compiles exactly one SPMD
    variant per jit bucket — the whole shard fan-out is one launch per
    bucket instead of the host loop's per-shard walk.

The SPMD-vs-host QPS columns are reported side by side as *data*, not a
gated claim: on this 1-core container the 8 "devices" are XLA virtual
host devices that serialize on the same core, so the collective fan-out
only adds orchestration over the host loop's identical total compute.
The throughput crossover is a real-multi-device claim (the ROADMAP's pod
rung); what this sweep pins down now is that switching mesh shape is a
config change with bit-identical results and stable compile counts.

``--smoke`` is the CI gate: shapes {1x2, 2x2}, tiny N, parity + recall +
trace-economy checks.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import subprocess
import sys

MESH_SHAPES = ((1, 8), (2, 4), (4, 2))  # (data, corpus)
SMOKE_SHAPES = ((1, 2), (2, 2))
BATCH_SIZES = (64, 256)
M, GAMMA, MBETA = 8, 8, 16
EF, K, D, CARD = 48, 10, 32, 8

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_PATH = os.path.join(ROOT, "BENCH_corpus_sharded.json")


def _child(args) -> None:
    """The whole sweep in one 8-virtual-device process."""
    import jax
    import numpy as np

    from repro.core import AcornConfig, ExecutionSpec, recall_at_k
    from repro.data import make_lcps_dataset, make_workload
    from repro.serve import EngineConfig, ServingEngine

    from benchmarks.common import timed_qps

    ds = make_lcps_dataset(n=args.n, d=D, card=CARD, seed=0)
    total = max(args.batches)
    wl = make_workload(ds, kind="equals", n_queries=2 * total, k=K, seed=1,
                       card=CARD)
    gt = wl.gt(ds)

    results = []
    for dp, cp in args.shapes:
        assert jax.local_device_count() >= dp * cp
        acorn = AcornConfig(M=M, gamma=GAMMA, m_beta=MBETA, ef_search=EF)
        for bs in args.batches:
            nq = 2 * bs
            eng = ServingEngine(
                ds.x, ds.table, acorn,
                EngineConfig(batch_size=bs, k=K, ef=EF, n_shards=cp,
                             spec=ExecutionSpec(data_parallel=dp,
                                                corpus_parallel=cp)))
            assert eng.spmd_mesh_shape() == (dp, cp)
            xq, preds = wl.xq[:nq], list(wl.predicates[:nq])

            def run(step):
                outs = []
                for s in range(0, nq, bs):
                    ids, _ = step(xq[s:s + bs], preds[s:s + bs])
                    outs.append(np.asarray(ids))
                return np.concatenate(outs)

            # the digest passes double as jit warmup for the timed runs
            ids_spmd = run(eng.search_batch)
            ids_host = run(eng.search_batch_host)
            qps_spmd = timed_qps(lambda: run(eng.search_batch), nq,
                                 warmup=0)
            qps_host = timed_qps(lambda: run(eng.search_batch_host), nq,
                                 warmup=0)
            results.append(dict(
                data=dp, corpus=cp, batch_size=bs, queries=nq,
                qps_spmd=qps_spmd, qps_host=qps_host,
                recall=float(recall_at_k(ids_spmd, gt[:nq])),
                spmd_traces={str(b): t
                             for b, t in eng.spmd_traces().items()},
                ids_digest_spmd=hashlib.sha256(
                    ids_spmd.tobytes()).hexdigest(),
                ids_digest_host=hashlib.sha256(
                    ids_host.tobytes()).hexdigest()))
    print("BENCH_CHILD_JSON:" + json.dumps(dict(results=results)))


def _sweep(shapes, batches, n):
    from benchmarks.common import virtual_cpu_env
    env = virtual_cpu_env(max(dp * cp for dp, cp in shapes))
    cmd = [sys.executable, "-m", "benchmarks.bench_corpus_sharded",
           "--child", "--n", str(n),
           "--batches", ",".join(str(b) for b in batches),
           "--shapes", ";".join(f"{dp}x{cp}" for dp, cp in shapes)]
    r = subprocess.run(cmd, env=env, cwd=ROOT, capture_output=True,
                       text=True, timeout=1800)
    if r.returncode != 0:
        raise RuntimeError(
            f"corpus-sharded bench child failed:\n"
            f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}")
    for line in r.stdout.splitlines():
        if line.startswith("BENCH_CHILD_JSON:"):
            return json.loads(line[len("BENCH_CHILD_JSON:"):])["results"]
    raise RuntimeError(f"no child payload:\n{r.stdout}")


def run(quick: bool = False, write_json: bool = True):
    shapes = SMOKE_SHAPES if quick else MESH_SHAPES
    batches = (64,) if quick else BATCH_SIZES
    n = 2048 if quick else 8192
    results = _sweep(shapes, batches, n)

    rows = [[f"mesh={r['data']}x{r['corpus']}", r["batch_size"],
             f"{r['qps_spmd']:.1f}", f"{r['qps_host']:.1f}",
             f"{r['recall']:.4f}"] for r in results]
    checks = {
        "spmd_ids_match_host_oracle": all(
            r["ids_digest_spmd"] == r["ids_digest_host"] for r in results),
        "recall_no_collapse": all(r["recall"] > 0.5 for r in results),
        # one compiled SPMD variant per jit bucket, no steady-state mints
        "one_trace_per_bucket": all(
            r["spmd_traces"] == {str(r["batch_size"]): 1} for r in results),
    }

    if write_json:
        payload = dict(
            config=dict(n=n, d=D, ef=EF, k=K, M=M, gamma=GAMMA,
                        m_beta=MBETA, quick=quick,
                        mesh_shapes=[list(s) for s in shapes],
                        batch_sizes=list(batches)),
            results=results,
            checks={k: bool(v) for k, v in checks.items()},
        )
        with open(OUT_PATH, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")

    return rows, checks


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-N CI gate; nonzero exit on parity/recall fail")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--batches", type=lambda s: tuple(
        int(b) for b in s.split(",")), default=BATCH_SIZES,
        help=argparse.SUPPRESS)
    ap.add_argument("--shapes", type=lambda s: tuple(
        tuple(int(v) for v in p.split("x")) for p in s.split(";")),
        default=MESH_SHAPES, help=argparse.SUPPRESS)
    ap.add_argument("--n", type=int, default=8192, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        _child(args)
        return
    rows, checks = run(quick=args.smoke, write_json=not args.smoke)
    for r in rows:
        print(",".join(str(x) for x in r))
    ok = True
    for name, passed in checks.items():
        print(f"  [{'smoke' if args.smoke else 'claim'}] {name}: "
              f"{'PASS' if passed else 'FAIL'}")
        ok &= bool(passed)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
