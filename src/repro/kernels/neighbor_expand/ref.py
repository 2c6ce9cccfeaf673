"""Pure-jnp oracle for the fused 2-hop neighbor expansion.

``neighbor_expand_ref`` is the *default* execution path of the search hot
loop (``use_kernel=False``): it reproduces, bit for bit, what the original
``get_neighbors`` strategies computed — gather the 2-hop candidate lists,
apply the predicate/visited filter, keep the first occurrence of each id,
pack the first M in candidate order — but replaces the O(C log C) stable
``argsort`` dedup with a *sort-free* first-occurrence scan: one scatter-min
of candidate positions into an id-indexed (B, n) tile plus one gather back
(:func:`first_occurrence_mask`).  Semantically identical because the
predicate/visited test is a pure function of the id, so "first passing
occurrence" equals "first occurrence that passes".

``neighbor_expand_argsort`` keeps the legacy argsort formulation as the
parity oracle for tests and as the baseline of
``benchmarks/bench_neighbor_expand.py``.

The scatter-min tile is O(B * n): past ``n ~ SCATTER_DEDUP_FACTOR * C *
log2 C`` its allocation/write cost overtakes the n-independent argsort
(measured crossover on CPU; at n = 2^20 the argsort is ~10x faster), so
:func:`use_scatter_dedup` picks the implementation per static shape at
trace time — both are bit-identical, the choice is purely cost.

Candidate scan order (must match Figure 4 and the Pallas kernel exactly):

  'filter'   — the 1-hop row itself; no dedup (ACORN-γ uncompressed).
  'compress' — row[:m_beta], then per tail entry t: [t, N(t)] row-major.
  'two_hop'  — row, then the j-th 2-hop neighbor of *every* 1-hop node
               before the (j+1)-th of any (breadth-first interleave).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

Array = jax.Array

INVALID = -1

# scatter-min dedup pays O(B * n) tile writes; stable argsort pays
# O(B * C log C) n-independent compares.  Measured CPU crossover sits near
# n = 8 * C * log2 C (above it the (B, n) tile falls out of cache and the
# argsort wins at any batch size).
SCATTER_DEDUP_FACTOR = 8


def use_scatter_dedup(n: int, c: int) -> bool:
    """Static (trace-time) cost choice between the two identical dedups."""
    return n <= SCATTER_DEDUP_FACTOR * c * math.log2(max(c, 2))


def gather_rows(nbr_table: Array, pos: Array, gids: Array) -> Array:
    """Neighbor rows for global ids: (..., ) -> (..., cap).

    Raw-array twin of ``repro.core.graph.neighbor_rows``: ids absent from
    the level (``pos`` -1) or invalid (< 0) yield all -1 rows.
    """
    n = pos.shape[0]
    cap = nbr_table.shape[1]
    if nbr_table.shape[0] == 0:
        return jnp.full(gids.shape + (cap,), INVALID, jnp.int32)
    r = pos[jnp.clip(gids, 0, n - 1)]
    present = (gids >= 0) & (r >= 0)
    rows = nbr_table[jnp.clip(r, 0, nbr_table.shape[0] - 1)]
    return jnp.where(present[..., None], rows, INVALID)


def expansion_candidates(row: Array, nbr_table: Array, pos: Array,
                         strategy: str, m_beta: int) -> Array:
    """Materialize the (B, C) candidate array in legacy scan order."""
    b = row.shape[0]
    if strategy == "filter":
        return row
    if strategy == "compress":
        head, tail = row[:, :m_beta], row[:, m_beta:]
        hop2 = gather_rows(nbr_table, pos, tail)          # (B, T, cap)
        two = jnp.concatenate([tail[..., None], hop2], axis=2)
        return jnp.concatenate([head, two.reshape(b, -1)], axis=1)
    if strategy == "two_hop":
        hop2 = gather_rows(nbr_table, pos, row)           # (B, cap, cap)
        inter = jnp.transpose(hop2, (0, 2, 1)).reshape(b, -1)
        return jnp.concatenate([row, inter], axis=1)
    raise ValueError(strategy)


def _passes(cand: Array, pass_mask: Optional[Array],
            visited: Optional[Array]) -> Array:
    """Validity + predicate + not-visited, all pure functions of the id."""
    ok = cand >= 0
    if pass_mask is not None:
        safe = jnp.clip(cand, 0, pass_mask.shape[1] - 1)
        ok &= jnp.take_along_axis(pass_mask, safe, axis=1)
    if visited is not None:
        safe = jnp.clip(cand, 0, visited.shape[1] - 1)
        ok &= ~jnp.take_along_axis(visited, safe, axis=1)
    return ok


def first_occurrence_mask(ids: Array, n: int) -> Array:
    """True at the first occurrence of each valid id — sort-free.

    (B, C) int32 ids in [-1, n) -> (B, C) bool.  Scatter-min of each
    candidate's position into an id-indexed (B, n) tile, then gather back
    and compare: a candidate is first iff its position IS the minimum for
    its id.  O(C + n) work instead of the O(C log C) stable argsort, and
    exactly the memory-access shape the Pallas kernel's VMEM onehot uses.
    """
    b, c = ids.shape
    safe = jnp.clip(ids, 0, n - 1)
    posn = jnp.broadcast_to(jnp.arange(c, dtype=jnp.int32), (b, c))
    rows = jnp.arange(b)[:, None]
    first = jnp.full((b, n), c, jnp.int32).at[rows, safe].min(
        jnp.where(ids >= 0, posn, c))
    return (ids >= 0) & (jnp.take_along_axis(first, safe, axis=1) == posn)


def _dedup_argsort(ids: Array) -> Array:
    """Legacy dedup: stable argsort + sorted-run first (batched)."""
    b = ids.shape[0]
    order = jnp.argsort(ids, axis=1, stable=True)
    s = jnp.take_along_axis(ids, order, axis=1)
    first_sorted = jnp.concatenate(
        [jnp.ones((b, 1), bool), s[:, 1:] != s[:, :-1]], axis=1)
    rows = jnp.arange(b)[:, None]
    mask = jnp.zeros(ids.shape, bool).at[rows, order].set(first_sorted)
    return mask & (ids >= 0)


def first_m_true_batched(ids: Array, ok: Array, m: int) -> Array:
    """Batched twin of ``core.search.first_m_true``: (B, C) -> (B, m)."""
    b = ids.shape[0]
    rank = jnp.cumsum(ok, axis=1) - 1
    scatter_to = jnp.where(ok & (rank < m), rank, m)
    out = jnp.full((b, m), INVALID, jnp.int32)
    return out.at[jnp.arange(b)[:, None], scatter_to].set(
        jnp.where(ok, ids, INVALID), mode="drop")


@functools.partial(jax.jit, static_argnames=("strategy", "m", "m_beta"))
def neighbor_expand_ref(row, nbr_table, pos, pass_mask=None, visited=None,
                        *, strategy: str, m: int, m_beta: int = 0):
    """Fused expansion, sort-free jnp reference (the default search path).

    row (B, cap) int32 1-hop ids (-1 padded); nbr_table (n_l, cap) level
    neighbor table; pos (n,) global id -> level row (-1 absent);
    pass_mask / visited (B, n) bool or None -> (B, m) int32 ids.
    """
    cand = expansion_candidates(row, nbr_table, pos, strategy, m_beta)
    ok = _passes(cand, pass_mask, visited)
    if strategy != "filter":   # filter scans a duplicate-free stored row
        n = pos.shape[0]
        if use_scatter_dedup(n, cand.shape[1]):
            ok &= first_occurrence_mask(cand, n)
        else:   # huge index: the (B, n) scatter tile would dominate
            ok &= _dedup_argsort(cand)
    return first_m_true_batched(cand, ok, m)


@functools.partial(jax.jit, static_argnames=("strategy", "m", "m_beta"))
def neighbor_expand_argsort(row, nbr_table, pos, pass_mask=None, visited=None,
                            *, strategy: str, m: int, m_beta: int = 0):
    """Legacy argsort-dedup expansion — test oracle and bench baseline."""
    cand = expansion_candidates(row, nbr_table, pos, strategy, m_beta)
    ok = _passes(cand, pass_mask, visited)
    if strategy != "filter":
        ok &= _dedup_argsort(cand)
    return first_m_true_batched(cand, ok, m)
