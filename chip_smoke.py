#!/usr/bin/env python3
"""Chip smoke: the ACORN serving path on a TPU, end to end, in one process.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # corpus-sharded SPMD over four chips

The deployment is the paper's SIFT1M-shaped LCPS setting (§6):
``make_lcps_dataset`` vectors (d = 128, f32) with one integer label of
cardinality 12, equality predicates (selectivity about 1/12), an ACORN-γ
index with M = 32, γ = 12, M_β = 64, and serving at k = 10, ef = 96.
Everything is generated from ``--seed``.

One chip: build through ``ServingEngine`` (``HybridIndex.build``), then
serve 256 equality queries and 64 wider ``Between`` queries on the label
as 8-query requests through ``ServingRuntime.submit`` — once with the jnp
search path and once with the Pallas kernels compiled
(``ExecutionSpec(use_kernel=True, interpret=False)``).  Checks, each of
which fails the run:

  * recall@10 >= 0.9 per query kind against the exact masked brute-force
    reference (``core/bruteforce.masked_topk``), computed on the chip;
  * kernel-on ids identical to kernel-off ids;
  * both §5.2 routes taken (graph and pre-filter), nothing shed;
  * every live device array on the TPU.

``--four-chips``: build a 4-shard engine with ``corpus_parallel=4``,
check that the mesh is (data 1, corpus 4) with each shard's vectors and
level-0 edges on its own chip, serve the same traffic through the
runtime, and compare the ids bit-for-bit with the host-loop oracle
(``search_batch_host``) and recall with the exact reference.

The corpus is cut from the repo's 2^20-row ``serve_1m`` size to
``N_USED`` rows (the reason is printed).  Times printed are smoke
numbers, not metrics.  The last line of stdout is
``{"ok": true, "device": {...}}``; with no TPU, or when a check fails,
the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

TARGET_N = 1 << 20       # configs/acorn.py serve_1m
N_USED = 1 << 19
CUT_REASON = (
    "the bulk builder runs exact KNN over every level's members "
    "(core/build.py knn_among), so build time grows with n^2: 2^19 rows "
    "build in about 440 s on one TPU v5e, and 2^20 rows would take about "
    "four times that, past the smoke's 1200 s limit")
D = 128
CARD = 12
K = 10
EF = 96
EQ_QUERIES = 256
RANGE_QUERIES = 64
RANGE_WIDTH = 2          # Between(label, lo, lo + 2): 3 of 12 labels
REQUEST_SIZE = 8
MIN_RECALL = 0.9


def log(tag: str, msg: str) -> None:
    print(f"[{tag}] {msg}", flush=True)


def make_traffic(ds, seed: int):
    """Equality queries (the LCPS mix) plus one wider range batch."""
    from repro.data import make_workload
    return {
        "equals": make_workload(ds, kind="equals", n_queries=EQ_QUERIES,
                                k=K, seed=seed + 1, card=CARD),
        "between": make_workload(ds, kind="between", n_queries=RANGE_QUERIES,
                                 k=K, seed=seed + 2, range_column="label",
                                 value_range=CARD, date_width=RANGE_WIDTH),
    }


def build_engine(ds, n_shards: int, spec, seed: int):
    from repro.core import AcornConfig
    from repro.serve import EngineConfig, ServingEngine
    acorn = AcornConfig(M=32, gamma=12, m_beta=64, ef_search=EF)
    cfg = EngineConfig(batch_size=256, k=K, ef=EF, n_shards=n_shards,
                       spec=spec)
    return ServingEngine(ds.x, ds.table, acorn, cfg, seed=seed)


def serve(engine, traffic, spec):
    """Submit every kind's queries as REQUEST_SIZE-query requests through
    the runtime, drain it, and return {kind: SearchResult}."""
    from repro.core import SearchRequest
    from repro.core.plan import SearchResult
    from repro.serve import RuntimeConfig, ServingRuntime
    engine.cfg = dataclasses.replace(engine.cfg, spec=spec)
    rt = ServingRuntime(engine, RuntimeConfig())
    tickets = {}
    for kind, wl in traffic.items():
        program = engine.compile(wl.predicates)
        tickets[kind] = [
            rt.submit(SearchRequest(
                xq=wl.xq[s:s + REQUEST_SIZE],
                predicates=program.take(slice(s, s + REQUEST_SIZE)), k=K))
            for s in range(0, wl.xq.shape[0], REQUEST_SIZE)]
    rt.pump()
    return {kind: SearchResult.concatenate([t.result(timeout=0) for t in ts])
            for kind, ts in tickets.items()}


def check_results(results, traffic, ds, failures):
    """Recall per kind against the exact reference, routes, shedding."""
    import numpy as np
    from repro.core import recall_at_k
    for kind, res in results.items():
        rec = recall_at_k(res.ids, traffic[kind].gt(ds))
        routes = dict(collections.Counter(str(r) for r in res.routes))
        log("recall", f"{kind}: recall@10 = {rec:.4f} over "
                      f"{res.n_queries} queries, routes {routes}")
        if not rec >= MIN_RECALL:
            failures.append(f"{kind} recall@10 {rec:.4f} < {MIN_RECALL}")
        if np.asarray(res.shed).any() or np.asarray(res.degraded).any():
            failures.append(f"{kind}: shed or degraded results")
    # "mixed": the shards' sketches disagreed, so both routes ran
    routes = set(str(r) for res in results.values() for r in res.routes)
    if not (routes & {"graph", "mixed"} and routes & {"prefilter", "mixed"}):
        failures.append(f"routes taken {sorted(routes)}: the router did "
                        "not exercise both the graph and pre-filter routes")


def check_placement(platform: str, failures):
    """Every live device array sits on a device of ``platform``."""
    import jax
    arrays = jax.live_arrays()
    off = [a for a in arrays
           if any(d.platform != platform for d in a.devices())]
    nbytes = sum(a.nbytes for a in arrays)
    log("placement", f"{len(arrays)} live device arrays, {nbytes} bytes, "
                     f"{len(off)} off the {platform}")
    if off:
        failures.append(f"{len(off)} device arrays are not on the "
                        f"{platform}")


def same_ids(a, b) -> bool:
    import numpy as np
    return all(np.array_equal(np.asarray(a[k].ids), np.asarray(b[k].ids))
               for k in a)


def make_data(n: int, seed: int):
    from repro.data import make_lcps_dataset
    t0 = time.perf_counter()
    ds = make_lcps_dataset(n=n, d=D, card=CARD, seed=seed)
    log("data", f"n={n} (target {TARGET_N}) d={D} labels={CARD} "
                f"generated in {time.perf_counter() - t0:.1f} s")
    return ds


def run_one_chip(n: int, seed: int, platform: str, interpret: bool = False):
    """The single-chip smoke; returns the list of failed checks."""
    from repro.core import ExecutionSpec
    failures = []
    ds = make_data(n, seed)
    traffic = make_traffic(ds, seed)
    jnp_spec = ExecutionSpec(use_kernel=False)
    kernel_spec = ExecutionSpec(use_kernel=True, interpret=interpret)

    t0 = time.perf_counter()
    engine = build_engine(ds, 1, jnp_spec, seed)
    build_s = time.perf_counter() - t0
    log("cut", f"target n={TARGET_N} used n={n} build {build_s:.1f} s; "
               f"reason: {CUT_REASON}")

    walls = {}
    results = {}
    for name, spec in (("jnp", jnp_spec), ("kernels", kernel_spec)):
        t0 = time.perf_counter()
        results[name] = serve(engine, traffic, spec)
        walls[name] = time.perf_counter() - t0
        log("serve", f"{name} path ({spec}): {EQ_QUERIES + RANGE_QUERIES} "
                     f"queries in {walls[name]:.1f} s incl. compile "
                     "(smoke number, not a metric)")
    log("path", f"spmd mesh {engine.spmd_mesh_shape()} "
                f"host-loop batches {engine.stats['host_loop_batches']} "
                f"spmd batches {engine.stats['spmd_batches']}")

    check_results(results["jnp"], traffic, ds, failures)
    parity = same_ids(results["jnp"], results["kernels"])
    log("parity", f"kernel ids (interpret={interpret}) identical to jnp "
                  f"ids: {parity}")
    if not parity:
        failures.append("kernel-on ids differ from kernel-off ids")
    check_placement(platform, failures)
    return failures


def run_four_chips(n: int, seed: int, platform: str):
    """The corpus-sharded SPMD path over four devices."""
    import jax
    from repro.core import ExecutionSpec, SearchRequest
    failures = []
    ds = make_data(n, seed)
    traffic = make_traffic(ds, seed)
    spec = ExecutionSpec(data_parallel=1, corpus_parallel=4)

    t0 = time.perf_counter()
    engine = build_engine(ds, 4, spec, seed)
    log("build", f"4 shards of {n // 4} rows in "
                 f"{time.perf_counter() - t0:.1f} s")
    mesh = engine.spmd_mesh_shape()
    log("mesh", f"spmd_mesh_shape={mesh}")
    if mesh != (1, 4):
        failures.append(f"mesh {mesh} != (1, 4)")

    t0 = time.perf_counter()
    spmd = serve(engine, traffic, spec)
    log("serve", f"SPMD: {EQ_QUERIES + RANGE_QUERIES} queries in "
                 f"{time.perf_counter() - t0:.1f} s incl. compile "
                 "(smoke number, not a metric)")
    corpus = engine.sharded_corpus()
    for name, arr in (("vectors", corpus.x),
                      ("level-0 edges", corpus.graph.neighbors[0])):
        pairs = sorted((s.index[0].start, s.device.id)
                       for s in arr.addressable_shards)
        log("placement", f"{name}: (shard, device id) = {pairs}")
        if (len({d for _, d in pairs}) != 4
                or [s for s, _ in pairs] != [0, 1, 2, 3]):
            failures.append(f"{name} are not one shard per device")

    host = {}
    for kind, wl in traffic.items():
        host[kind] = engine.search_batch_host(SearchRequest(
            xq=wl.xq, predicates=engine.compile(wl.predicates), k=K,
            ef=EF))
    parity = same_ids(spmd, host)
    log("parity", f"SPMD ids bit-identical to the host-loop oracle: "
                  f"{parity}")
    if not parity:
        failures.append("SPMD ids differ from the host-loop oracle")
    log("path", f"spmd batches {engine.stats['spmd_batches']} host-loop "
                f"batches {engine.stats['host_loop_batches']} (oracle)")
    check_results(spmd, traffic, ds, failures)
    check_placement(platform, failures)
    if len(jax.devices()) < 4:
        failures.append("fewer than four devices")
    return failures


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the corpus-sharded four-chip path")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n", type=int, default=N_USED,
                    help="corpus rows (smaller only to debug)")
    args = ap.parse_args()
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}; run from a "
              "checkout of the repo", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.compile_cache import configure_compile_cache
    cache = configure_compile_cache()

    import jax
    devices = jax.devices()
    dev = devices[0]
    log("device", f"platform={dev.platform} kind={dev.device_kind} "
                  f"count={len(devices)} jax={jax.__version__}")
    log("cache", f"compile cache: {cache}")
    if dev.platform != "tpu":
        print("chip_smoke: JAX finds no TPU; this smoke runs only on the "
              "chip", file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, found {len(devices)}",
              file=sys.stderr)
        return 1

    t0 = time.perf_counter()
    if args.four_chips:
        failures = run_four_chips(args.n, args.seed, dev.platform)
    else:
        failures = run_one_chip(args.n, args.seed, dev.platform)
    log("done", f"{time.perf_counter() - t0:.1f} s")
    for f in failures:
        log("FAIL", f)
    if failures:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
