"""Pallas TPU kernel: EmbeddingBag (ragged gather + bag reduce).

JAX has no native EmbeddingBag (taxonomy §RecSys); this is the recsys hot
path: for each example, gather up to L rows of a huge HBM-resident embedding
table and reduce them (sum/mean).  Same DMA double-buffering structure as
gather_distance: row j+1's copy overlaps row j's accumulate.

Grid: one step per bag (batch row).  The accumulator lives in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _embedding_bag_kernel(ids_ref, table_ref, o_ref, row_ref, acc_ref, sems,
                          *, l: int, v: int, mode: str):
    """ids_ref (1, l) SMEM; table_ref (v, d) ANY/HBM; o_ref (1, d) VMEM;
    row_ref (2, 1, d) VMEM; acc_ref (1, d) VMEM; sems: 2 DMA."""

    acc_ref[...] = jnp.zeros_like(acc_ref)

    def start(j, slot):
        idx = jnp.clip(ids_ref[0, j], 0, v - 1)
        pltpu.make_async_copy(table_ref.at[pl.ds(idx, 1)], row_ref.at[slot],
                              sems.at[slot]).start()

    start(0, 0)

    def body(j, cnt):
        slot = jax.lax.rem(j, 2)

        @pl.when(j + 1 < l)
        def _():
            start(j + 1, jax.lax.rem(j + 1, 2))

        idx = jnp.clip(ids_ref[0, j], 0, v - 1)
        pltpu.make_async_copy(table_ref.at[pl.ds(idx, 1)], row_ref.at[slot],
                              sems.at[slot]).wait()
        valid = ids_ref[0, j] >= 0
        acc_ref[...] += jnp.where(valid, row_ref[slot], 0.0)
        return cnt + jnp.where(valid, 1, 0)

    cnt = jax.lax.fori_loop(0, l, body, jnp.asarray(0, jnp.int32))
    if mode == "mean":
        o_ref[...] = acc_ref[...] / jnp.maximum(cnt, 1).astype(jnp.float32)
    else:
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("mode", "interpret"))
def embedding_bag_pallas(ids, table, mode: str = "sum",
                         interpret: bool = False):
    """ids (B, L) int32 (-1 padded), table (V, D) -> (B, D)."""
    b, l = ids.shape
    v, d = table.shape
    kern = functools.partial(_embedding_bag_kernel, l=l, v=v, mode=mode)
    return pl.pallas_call(
        kern,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, l), lambda i: (i, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, d), table.dtype),
        scratch_shapes=[pltpu.VMEM((2, 1, d), table.dtype),
                        pltpu.VMEM((1, d), table.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
        interpret=interpret,
    )(ids, table)
