"""Fused neighbor-expansion microbenchmark: strategy x cap x m_beta x impl.

Times one batched expansion call (the per-hop inner op of the ACORN beam
search) over a synthetic level of ``N_NODES`` nodes for three
implementations:

  argsort — the legacy path: materialize the ~(cap - m_beta) x (cap + 1)
            candidate array, stable-argsort dedup, first-M pack
            (``neighbor_expand_argsort``);
  fused   — the sort-free jnp reference that now backs the default search
            path (``neighbor_expand_ref``: scatter-min first-occurrence,
            no sort; at N_NODES=8192 every sweep point sits on the
            scatter side of the ``use_scatter_dedup`` crossover — past
            n ~ 8 C log2 C the ref auto-falls back to argsort);
  kernel  — the Pallas kernel in interpret mode (``use_kernel=True``; on
            CPU this measures interpreter overhead, NOT the TPU lowering —
            recorded for completeness, the claim below is argsort vs
            fused).

Writes ``BENCH_neighbor_expand.json`` at the repo root.  Claims validated:

  * parity: all three implementations return identical ids at every point;
  * the fused path beats the argsort path at cap >= 32 for the 2-hop
    strategies (compress / two_hop) — the regime ROADMAP flagged as the
    dominant per-hop cost.
"""
from __future__ import annotations

import json
import os

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.neighbor_expand import (neighbor_expand,
                                           neighbor_expand_argsort,
                                           neighbor_expand_ref)

from .common import interpret_kernels

N_NODES = 8192
B = 16
M = 16
CAPS = (16, 32, 64)
IMPLS = ("argsort", "fused", "kernel")

OUT_PATH = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_neighbor_expand.json")


def _make_level(cap: int, seed: int = 0):
    """Synthetic fully-present level: ids are rows, table is random."""
    rng = np.random.default_rng(seed)
    tbl = rng.integers(0, N_NODES, size=(N_NODES, cap)).astype(np.int32)
    tbl[rng.random((N_NODES, cap)) < 0.1] = -1
    pos = np.arange(N_NODES, dtype=np.int32)
    row = rng.integers(0, N_NODES, size=(B, cap)).astype(np.int32)
    row[rng.random((B, cap)) < 0.1] = -1
    pm = rng.random((B, N_NODES)) < 0.4
    vis = rng.random((B, N_NODES)) < 0.1
    return (jnp.asarray(row), jnp.asarray(tbl), jnp.asarray(pos),
            jnp.asarray(pm), jnp.asarray(vis))


def best_of_qps(fn, n_queries: int, warmup: int = 3, reps: int = 5,
                inner: int = 3) -> float:
    """Best-of-``reps`` QPS (each rep times ``inner`` back-to-back calls).

    A sub-10ms op on a shared-core CI host sees multi-ms scheduler
    preemptions; the *minimum* window is the standard noise-robust
    estimator for such microbenchmarks (``timeit`` semantics), where the
    mean ``benchmarks.common.timed_qps`` uses for long-running sweeps
    would be dominated by the noise floor.
    """
    for _ in range(warmup):
        jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(inner):
            jax.block_until_ready(fn())
        best = min(best, (time.perf_counter() - t0) / inner)
    return n_queries / best


def _runner(impl: str, args, strategy: str, m_beta: int):
    row, tbl, pos, pm, vis = args
    kw = dict(strategy=strategy, m=M, m_beta=m_beta)
    if impl == "argsort":
        return lambda: neighbor_expand_argsort(row, tbl, pos, pm, vis, **kw)
    if impl == "fused":
        return lambda: neighbor_expand_ref(row, tbl, pos, pm, vis, **kw)
    return lambda: neighbor_expand(row, tbl, pos, pm, vis, use_kernel=True,
                                   interpret=interpret_kernels(), **kw)


def _points(quick: bool):
    caps = CAPS[:2] if quick else CAPS
    for cap in caps:
        for strategy in ("filter", "compress", "two_hop"):
            m_betas = ((0, cap // 2) if strategy == "compress" else (0,))
            for m_beta in m_betas:
                yield strategy, cap, m_beta


def run(quick: bool = False, write_json: bool = True):
    rows, results = [], []
    parity_ok = True
    for strategy, cap, m_beta in _points(quick):
        args = _make_level(cap)
        outs = {}
        point = dict(strategy=strategy, cap=cap, m_beta=m_beta)
        for impl in IMPLS:
            fn = _runner(impl, args, strategy, m_beta)
            outs[impl] = np.asarray(fn())
            # expansions/s: one call expands B lanes
            eps = best_of_qps(fn, B, reps=4 if quick else 8)
            point[f"eps_{impl}"] = eps
        same = (np.array_equal(outs["argsort"], outs["fused"])
                and np.array_equal(outs["argsort"], outs["kernel"]))
        parity_ok &= same
        point["parity"] = bool(same)
        point["fused_speedup"] = point["eps_fused"] / point["eps_argsort"]
        results.append(point)
        rows.append([strategy, cap, m_beta,
                     f"{point['eps_argsort']:.0f}",
                     f"{point['eps_fused']:.0f}",
                     f"{point['eps_kernel']:.0f}",
                     f"{point['fused_speedup']:.2f}x",
                     "ok" if same else "MISMATCH"])

    def fused_wins(p):
        return p["eps_fused"] > p["eps_argsort"]

    big_2hop = [p for p in results
                if p["cap"] >= 32 and p["strategy"] != "filter"]
    checks = {
        "parity_all_impls": parity_ok,
        "fused_beats_argsort_cap32_2hop":
            bool(big_2hop) and all(fused_wins(p) for p in big_2hop),
    }

    if write_json:
        payload = dict(
            config=dict(n=N_NODES, b=B, m=M, caps=list(CAPS), quick=quick,
                        impls=list(IMPLS)),
            results=results,
            checks={k: bool(v) for k, v in checks.items()},
        )
        with open(OUT_PATH, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")

    return rows, checks


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sweep, no JSON; nonzero exit on failed claim")
    args = ap.parse_args()
    rows, checks = run(quick=args.smoke, write_json=not args.smoke)
    header = ["strategy", "cap", "m_beta", "eps_argsort", "eps_fused",
              "eps_kernel", "fused_speedup", "parity"]
    print(",".join(header))
    for r in rows:
        print(",".join(str(x) for x in r))
    ok = True
    for name, passed in checks.items():
        print(f"  [{'smoke' if args.smoke else 'claim'}] {name}: "
              f"{'PASS' if passed else 'FAIL'}")
        ok &= bool(passed)
    return 0 if ok else 1


if __name__ == "__main__":
    import sys

    sys.exit(main())
