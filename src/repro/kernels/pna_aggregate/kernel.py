"""Pallas TPU kernel: fused PNA multi-aggregator (mean/max/min/std).

PNA (arXiv:2004.05718) aggregates each node's neighbor messages with four
reducers in parallel, then applies three degree scalers.  A naive
implementation makes four passes over the messages; this kernel fuses all
four into one pass over the adjacency tile: sum and sum-of-squares ride the
MXU (adjacency is a 0/1 matrix), max/min use masked vector reductions.

Contract (dense-batched regime — e.g. the ``molecule`` shape's padded small
graphs): adj (B, N, N) float {0,1}, feats (B, N, F) -> (B, N, 4F) laid out
[mean | max | min | std].  The sparse regime (segment_sum over edge lists)
is handled by ref.pna_aggregate_segment_ref + models/gnn.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pna_kernel(adj_ref, feat_ref, o_ref, *, n: int, f: int):
    adj = adj_ref[0]      # (N, N) row = destination, col = source
    h = feat_ref[0]       # (N, F)
    cnt = jnp.sum(adj, axis=1, keepdims=True)          # (N, 1)
    denom = jnp.maximum(cnt, 1.0)
    s = jax.lax.dot_general(adj, h, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    ssq = jax.lax.dot_general(adj, h * h, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    mean = s / denom
    var = jnp.maximum(ssq / denom - mean * mean, 0.0)
    std = jnp.sqrt(var + 1e-12)  # +eps: d/dx sqrt has infinite grad at 0
    m = adj[:, :, None] > 0                            # (N, N, 1)
    hmax = jnp.max(jnp.where(m, h[None, :, :], -1e30), axis=1)
    hmin = jnp.min(jnp.where(m, h[None, :, :], 1e30), axis=1)
    has = cnt > 0
    hmax = jnp.where(has, hmax, 0.0)
    hmin = jnp.where(has, hmin, 0.0)
    o_ref[0] = jnp.concatenate([mean, hmax, hmin, std], axis=1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pna_aggregate_pallas(adj, feats, interpret: bool = False):
    """adj (B, N, N) f32 in {0,1}, feats (B, N, F) -> (B, N, 4F)."""
    b, n, _ = adj.shape
    f = feats.shape[-1]
    kern = functools.partial(_pna_kernel, n=n, f=f)
    return pl.pallas_call(
        kern,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, n, n), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, n, f), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, n, 4 * f), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n, 4 * f), jnp.float32),
        interpret=interpret,
    )(adj, feats)
