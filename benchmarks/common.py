"""Shared benchmark harness.

CPU-scale note (DESIGN.md §7): the paper's absolute QPS comes from a
96-vCPU host; this container has one core and jit-interpreted TPU kernels.
Benchmarks therefore validate the paper's *orderings and ratio bands*
(which method wins where, and by roughly how much) at n in the 10^4..10^5
range, with identical (n, d, B) across figures so jit caches are shared.

Every module writes a CSV into experiments/bench/ and returns rows for
benchmarks.run's combined report.
"""
from __future__ import annotations

import os
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (ExecutionSpec, ann_search, hybrid_search,
                        masked_topk, prefilter_search, postfilter_search,
                        recall_at_k)

BENCH_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                         "bench")

# standardized workload geometry (shared jit caches across figures)
N = 12288
D = 32
B = 64
K = 10
EF_SWEEP = (16, 32, 64, 128)


def interpret_kernels() -> bool:
    """Whether Pallas kernels must run in interpret mode here: only on the
    CPU, which has no Mosaic backend (a CPU number then measures the
    interpreter, not the kernel).  On a TPU they compile."""
    return jax.devices()[0].platform == "cpu"


def virtual_cpu_env(n_devices: int) -> Dict[str, str]:
    """Environment for a child process that rehearses an ``n_devices``
    mesh on virtual CPU devices.

    Only a CPU host starts such children.  On an accelerator this process,
    having touched JAX, holds the chip: a child would either fight it for
    the chip or quietly measure the CPU.  There the sweep stops with an
    error instead (``python chip_smoke.py --four-chips`` drives the
    corpus-sharded path on a four-chip host, in one process)."""
    platform = jax.devices()[0].platform
    if platform != "cpu":
        raise RuntimeError(
            f"this sweep rehearses meshes on virtual CPU devices in child "
            f"processes and cannot run on a {platform} host, where this "
            "process holds the chip; run it with JAX_PLATFORMS=cpu, or "
            "drive the chip with chip_smoke.py")
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    env["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={n_devices}".strip())
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"
    return env


def out_path(name: str) -> str:
    os.makedirs(BENCH_DIR, exist_ok=True)
    return os.path.join(BENCH_DIR, name)


def timed_qps(fn: Callable, n_queries: int, warmup: int = 1,
              runs: int = 2) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(runs):
        jax.block_until_ready(fn())
    dt = (time.perf_counter() - t0) / runs
    return n_queries / dt


def write_csv(name: str, header: List[str], rows: List[List]) -> str:
    path = out_path(name)
    with open(path, "w") as f:
        f.write(",".join(header) + "\n")
        for r in rows:
            f.write(",".join(str(x) for x in r) + "\n")
    return path


# ---------------------------------------------------------------------------
# method runners: each returns dict(recall=..., qps=..., dist_comps=...)
# ---------------------------------------------------------------------------


def run_acorn(graph, x, wl, ds, ef: int, variant: str, m: int, m_beta: int,
              compressed: bool = True, use_kernel: bool = False,
) -> Dict:
    masks, gt = wl.masks(ds), wl.gt(ds)
    kw = dict(k=K, ef=ef, variant=variant, m=m, m_beta=m_beta,
              compressed_level0=compressed and variant == "acorn-gamma",
              max_expansions=4 * ef,
              spec=ExecutionSpec(use_kernel=use_kernel,
                                 interpret=interpret_kernels()))
    ids, _, st = hybrid_search(graph, x, wl.xq, masks, **kw)
    qps = timed_qps(lambda: hybrid_search(graph, x, wl.xq, masks, **kw)[0],
                    wl.xq.shape[0])
    return dict(recall=recall_at_k(ids, gt), qps=qps,
                dist_comps=float(jnp.mean(st.dist_comps)))


def run_prefilter(x, wl, ds) -> Dict:
    masks, gt = wl.masks(ds), wl.gt(ds)
    ids, _ = prefilter_search(wl.xq, x, masks, K)
    qps = timed_qps(lambda: prefilter_search(wl.xq, x, masks, K)[0],
                    wl.xq.shape[0])
    return dict(recall=recall_at_k(ids, gt), qps=qps,
                dist_comps=float(jnp.mean(masks.sum(axis=1))))


def run_postfilter(graph, x, wl, ds, ef: int, m: int) -> Dict:
    masks, gt = wl.masks(ds), wl.gt(ds)
    s = wl.avg_selectivity(ds)
    ids, _ = postfilter_search(graph, x, wl.xq, masks, K, selectivity=s,
                               ef=ef, m=m)
    qps = timed_qps(
        lambda: postfilter_search(graph, x, wl.xq, masks, K, selectivity=s,
                                  ef=ef, m=m)[0], wl.xq.shape[0])
    # dist comps of the underlying ANN oversearch
    import math
    from repro.core.baselines import _bucket
    kk = _bucket(max(int(math.ceil(K / max(s, 1e-6))), K), K, 4096)
    ef_eff = _bucket(max(ef, kk), max(ef, K), max(4096, ef))
    _, _, st = ann_search(graph, x, wl.xq, k=kk, ef=ef_eff, m=m)
    return dict(recall=recall_at_k(ids, gt), qps=qps,
                dist_comps=float(jnp.mean(st.dist_comps)))


def run_oracle(oidx, wl, ds, ef: int) -> Dict:
    gt = wl.gt(ds)
    ids_all, dc = [], []
    for q, pred in enumerate(wl.predicates):
        ids, _, st = oidx.search(pred.value, wl.xq[q:q + 1], k=K, ef=ef)
        ids_all.append(ids)
        dc.append(float(st.dist_comps[0]))
    ids = jnp.concatenate(ids_all)
    # QPS on one representative partition (batched)
    pid = wl.predicates[0].value
    qps = timed_qps(lambda: oidx.search(pid, wl.xq, K, ef=ef)[0],
                    wl.xq.shape[0])
    return dict(recall=recall_at_k(ids, gt), qps=qps,
                dist_comps=float(np.mean(dc)))


def qps_at_recall(points: List[Dict], target: float = 0.9) -> Optional[float]:
    """Best QPS among sweep points reaching the target recall."""
    ok = [p["qps"] for p in points if p["recall"] >= target]
    return max(ok) if ok else None
