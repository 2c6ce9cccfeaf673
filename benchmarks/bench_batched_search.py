"""Batched hybrid-search throughput: jit buckets x gather_distance kernel.

Measures QPS of the bucketed ``search_batch`` pipeline at batch sizes
{1, 16, 64, 256}, kernel-off (pure-jnp distances) vs kernel-on (the
gather_distance Pallas kernel; interpret mode on CPU — compiled on TPU,
where the kernel numbers are the ones that matter).  Writes
``BENCH_batched_search.json`` at the repo root.

Claims validated:
  * batching pays: batch-64 QPS strictly above batch-1 QPS (kernel-off);
  * kernel-on and kernel-off return identical neighbor ids;
  * recall does not collapse (guards the --smoke CI gate).

Configuration note: this benchmark runs the *uncompressed* ACORN-γ config
(Fig 4a 'filter' lookups, ``compress=False``) so the per-expansion cost is
the bounded gather+distance+merge pipeline itself — the thing batching and
the kernel accelerate.  The compressed/2-hop configs spend most of their
per-hop time in the dedup sort of the 2-hop candidate expansion, which is
orthogonal to batch execution and covered by fig7/fig12.
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (ExecutionSpec, VariantCache, build_acorn_gamma,
                        recall_at_k, search_batch)
from repro.data import make_lcps_dataset, make_workload

from .common import interpret_kernels, timed_qps

BATCH_SIZES = (1, 16, 64, 256)
M, GAMMA, MBETA = 8, 8, 16
EF, K, D, CARD = 48, 10, 32, 8

OUT_PATH = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_batched_search.json")


def _make_runner(graph, x, xq, masks, bs: int, nq: int, use_kernel: bool):
    """Process nq queries in chunks of bs through a fresh variant cache."""
    cache = VariantCache()

    def run_once():
        outs = []
        for s in range(0, nq, bs):
            ids, _, _ = search_batch(
                graph, x, xq[s:s + bs], masks[s:s + bs], k=K, ef=EF,
                variant="acorn-gamma", m=M, m_beta=MBETA,
                compressed_level0=False,
                spec=ExecutionSpec(use_kernel=use_kernel,
                                   interpret=interpret_kernels()),
                buckets=(bs,), cache=cache)
            outs.append(ids)
        return jnp.concatenate(outs)

    return run_once


def run(quick: bool = False, write_json: bool = True):
    n = 2048 if quick else 8192
    total = 64 if quick else 256
    ds = make_lcps_dataset(n=n, d=D, card=CARD, seed=0)
    wl = make_workload(ds, kind="equals", n_queries=total, k=K, seed=1,
                      card=CARD)
    masks = wl.masks(ds)
    graph = build_acorn_gamma(ds.x, jax.random.PRNGKey(0), M=M, gamma=GAMMA,
                              m_beta=MBETA, compress=False)

    rows, results = [], []
    ids_by_kernel = {}
    for use_kernel in (False, True):
        for bs in BATCH_SIZES:
            # enough queries to amortize timing noise without making the
            # batch-1 sweep O(total) dispatches
            nq = min(total, 16 if bs == 1 else 2 * bs)
            if nq >= bs:
                nq = (nq // bs) * bs  # full launches only
            # else: one padded launch; QPS still counts real queries
            runner = _make_runner(graph, ds.x, wl.xq, masks, bs, nq,
                                  use_kernel)
            qps = timed_qps(runner, nq)
            ids = runner()
            rec = float(recall_at_k(ids, wl.gt(ds)[:nq]))
            if bs == 64:
                ids_by_kernel[use_kernel] = np.asarray(ids)
            results.append(dict(use_kernel=use_kernel, batch_size=bs,
                                queries=nq, qps=qps, recall=rec))
            rows.append([f"kernel={int(use_kernel)}", bs, f"{qps:.1f}",
                         f"{rec:.4f}"])

    def qps_of(kernel, bs):
        return next(r["qps"] for r in results
                    if r["use_kernel"] is kernel and r["batch_size"] == bs)

    checks = {
        "batch64_qps_above_batch1": qps_of(False, 64) > qps_of(False, 1),
        "kernel_ids_match_reference": bool(
            np.array_equal(ids_by_kernel[True], ids_by_kernel[False])),
        "recall_no_collapse": all(r["recall"] > 0.5 for r in results),
    }

    if write_json:
        payload = dict(
            config=dict(n=n, d=D, total_queries=total, ef=EF, k=K, M=M,
                        gamma=GAMMA, m_beta=MBETA, quick=quick,
                        batch_sizes=list(BATCH_SIZES)),
            results=results,
            checks={k: bool(v) for k, v in checks.items()},
        )
        with open(OUT_PATH, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")

    return rows, checks
