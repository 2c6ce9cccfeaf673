"""jit'd public EmbeddingBag op (+ custom VJP so training works through it).

The Pallas kernel is forward-only (serving hot path); the backward pass is
the standard scatter-add, expressed via the ref implementation's VJP.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import embedding_bag_pallas
from .ref import embedding_bag_ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _bag(ids, table, mode, interpret):
    return embedding_bag_pallas(ids, table, mode, interpret=interpret)


def _bag_fwd(ids, table, mode, interpret):
    return _bag(ids, table, mode, interpret), (ids, table.shape)


def _bag_bwd(mode, interpret, res, g):
    ids, tshape = res
    valid = (ids >= 0)[..., None]
    if mode == "mean":
        cnt = jnp.maximum(jnp.sum(ids >= 0, axis=1, keepdims=True),
                          1).astype(g.dtype)
        g = g / cnt
    contrib = jnp.where(valid, g[:, None, :], 0.0)  # (B, L, D)
    flat_ids = jnp.clip(ids.reshape(-1), 0, tshape[0] - 1)
    flat = contrib.reshape(-1, tshape[1])
    dtable = jnp.zeros(tshape, g.dtype).at[flat_ids].add(flat)
    return None, dtable


_bag.defvjp(_bag_fwd, _bag_bwd)


@functools.partial(jax.jit, static_argnames=("mode", "use_kernel",
                                             "interpret"))
def embedding_bag(ids, table, mode: str = "sum", use_kernel: bool = True,
                  interpret: bool = False):
    """ids (B, L) int32 (-1 padded), table (V, D) -> (B, D)."""
    if not use_kernel:
        return embedding_bag_ref(ids, table, mode)
    return _bag(ids, table, mode, interpret)
