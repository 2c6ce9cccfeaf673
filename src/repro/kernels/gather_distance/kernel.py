"""Pallas TPU kernel: neighbor-row gather + fused distance.

The inner hot op of ACORN's graph traversal (Algorithm 2 line 9-14): given
the filtered neighbor ids of the node being expanded, fetch their vectors
and compute distances to the query.  On TPU the vectors live in HBM; every
row of a lane is pulled with its own async DMA into an (M, d) VMEM tile —
all M copies are in flight at once and share one semaphore — and the
distances are one vector reduction over that tile.

Grid: one step per query lane.  The lane's ids arrive in SMEM (they drive
the DMA addresses).  Blocks carry a squeezed leading lane axis so that
each block's last two dims equal the array's, which is what Mosaic's
(8, 128) block rule accepts for any M and d.  Invalid (-1) ids fetch row 0
and are masked to +inf by the wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gather_distance_kernel(ids_ref, q_ref, x_ref, o_ref, rows_ref, sem,
                            *, m: int, n: int, metric: str):
    """ids_ref (1, m) SMEM; q_ref (1, d) VMEM; x_ref (n, d) HBM;
    o_ref (m, 1) VMEM; rows_ref (m, d) VMEM scratch; sem: one DMA sem."""

    def copy(j):
        idx = jnp.clip(ids_ref[0, j], 0, n - 1)
        return pltpu.make_async_copy(x_ref.at[pl.ds(idx, 1)],
                                     rows_ref.at[pl.ds(j, 1)], sem)

    def start(j, _):
        copy(j).start()
        return 0

    def wait(j, _):
        copy(j).wait()
        return 0

    jax.lax.fori_loop(0, m, start, 0)
    jax.lax.fori_loop(0, m, wait, 0)
    rows = rows_ref[...]
    q = q_ref[...]
    if metric == "l2":
        diff = rows - q
        o_ref[...] = jnp.sum(diff * diff, axis=1, keepdims=True)
    else:  # ip (negated: lower = better, matching search semantics)
        o_ref[...] = -jnp.sum(rows * q, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("metric", "interpret"))
def gather_distance_pallas(ids, q, x, metric: str = "l2",
                           interpret: bool = False):
    """ids (B, M) int32 (-1 padded), q (B, d), x (n, d) -> dists (B, M)."""
    if metric not in ("l2", "ip"):
        raise ValueError(metric)
    b, m = ids.shape
    n, d = x.shape
    kern = functools.partial(_gather_distance_kernel, m=m, n=n, metric=metric)
    out = pl.pallas_call(
        kern,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((None, 1, m), lambda i: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((None, 1, d), lambda i: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((None, m, 1), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, m, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((m, d), x.dtype),
                        pltpu.SemaphoreType.DMA(())],
        interpret=interpret,
    )(ids[:, None, :], q[:, None, :], x)
    return jnp.where(ids >= 0, out[:, :, 0], jnp.inf)
