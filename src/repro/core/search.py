"""ACORN predicate-subgraph traversal (paper Algorithms 1-2, Figure 4).

TPU adaptation (DESIGN.md §2): the greedy descent and the level-0 beam
search run as *explicitly batched* ``jax.lax.while_loop``s over fixed-size
sorted beams; all heaps/sets become fixed-shape masked arrays.  Per-lane
convergence follows the vmap-of-while_loop contract: the loop runs until
every lane's condition is false, and a converged lane's carry is frozen.

Batching the loop state (rather than ``vmap``-ing a scalar search) lets
every beam-expansion distance computation issue as ONE call over the whole
query batch, which routes through the ``gather_distance`` Pallas kernel
(DMA-gathered rows + fused distance) when ``use_kernel=True`` — on the
CPU the kernel runs only in interpret mode (``interpret=True``, asked for
explicitly); ``use_kernel=False`` selects the pure-jnp reference path.  The per-expansion beam update is a
bounded sorted-merge (``repro.kernels.filtered_topk.bounded_sorted_merge``)
instead of a full ``argsort`` of the (ef + M) concatenation: the beam is
already sorted, so only the M candidates need ordering.

Neighbor-lookup strategies (Figure 4):
  'plain'    — first entries of N^l(c), no predicate (HNSW search,
               construction-time metadata-agnostic lookups, and every
               variant's upper-level descent — see ``_search_impl``).
  'filter'   — scan N^l(c), keep predicate-passing, truncate to M (ACORN-γ,
               uncompressed levels — Fig 4a).
  'compress' — first M_β entries filtered directly; remaining entries
               expanded to their own neighbor lists (2-hop recovery of
               pruned edges), filtered, truncated to M (Fig 4b).
  'two_hop'  — full 1-hop + 2-hop expansion, filter, truncate to M
               (ACORN-1 — Fig 4c).

The filter/compress/two_hop lookups run through the fused
``repro.kernels.neighbor_expand`` subsystem: gather + predicate/visited
filter + first-occurrence dedup + first-M pack in one op (sort-free jnp
reference by default; a per-lane Pallas kernel behind ``expand_kernel`` /
``use_kernel``), replacing the per-hop stable-argsort dedup of the
flattened 2-hop candidate array.  ``first_m_true`` / ``dedup_mask`` below
are the original single-lane primitives, kept as the spec the fused op is
property-tested against (tests/test_search_invariants.py).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.filtered_topk.merge import bounded_sorted_merge
from repro.kernels.gather_distance.ops import gather_distance
from repro.kernels.gather_distance.ref import gather_distance_ref
from repro.kernels.neighbor_expand.kernel import (neighbor_expand_packed,
                                                  pack_bitmap)
from repro.kernels.neighbor_expand.ops import neighbor_expand

from .graph import INVALID, LayeredGraph, neighbor_rows
from .plan import ExecutionSpec, resolve_execution_spec

Array = jax.Array

INF = jnp.inf


class SearchStats(NamedTuple):
    dist_comps: Array  # per-query number of distance computations
    hops: Array        # per-query number of expanded nodes (level 0)


# ---------------------------------------------------------------------------
# small fixed-shape helpers
# ---------------------------------------------------------------------------


def first_m_true(ids: Array, ok: Array, m: int) -> Array:
    """Pack the first m ids where ok, preserving order; -1 padded. (C,)->(m,)."""
    rank = jnp.cumsum(ok) - 1
    scatter_to = jnp.where(ok & (rank < m), rank, m)
    out = jnp.full((m,), INVALID, jnp.int32)
    return out.at[scatter_to].set(jnp.where(ok, ids, INVALID), mode="drop")


def dedup_mask(ids: Array) -> Array:
    """True at the first occurrence of each valid id (order preserved)."""
    c = ids.shape[0]
    order = jnp.argsort(ids, stable=True)
    s = ids[order]
    first_sorted = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]])
    # within equal ids, argsort(stable) keeps original order -> first in the
    # sorted run is the earliest original occurrence
    mask = jnp.zeros((c,), bool).at[order].set(first_sorted)
    return mask & (ids >= 0)


def _lanes(active: Array, ndim: int) -> Array:
    """Broadcast a (B,) lane mask against an ndim-rank batched array."""
    return jnp.reshape(active, active.shape + (1,) * (ndim - 1))


# ---------------------------------------------------------------------------
# neighbor lookup (Figure 4)
# ---------------------------------------------------------------------------


def get_neighbors(
    graph: LayeredGraph,
    level: int,
    c: Array,
    pass_mask: Optional[Array],
    strategy: str,
    m: int,
    m_beta: int,
    visited: Optional[Array] = None,
    use_kernel: bool = False,
    interpret: bool = False,
) -> Array:
    """Return up to ``m`` neighbor ids of node ``c`` for the query predicate.

    ``pass_mask=None`` is accepted by every strategy and means "all nodes
    pass" (the unfiltered substrate).  ``visited`` (when given) is applied
    *before* the first-M truncation: the M-bound exists to cap distance
    computations per expansion (§6.3.1 'Bounded Degree'); already-visited
    nodes cost no distance computation, and truncating them away starves
    exploration in dense regions (visible as an ACORN-1 recall plateau —
    EXPERIMENTS.md §Repro-notes).

    The filter/compress/two_hop lookups (Figure 4) run through the fused
    ``repro.kernels.neighbor_expand`` op: ``use_kernel=False`` (default)
    selects its sort-free pure-jnp reference, ``use_kernel=True`` the
    Pallas kernel (``interpret=True`` off-TPU) — bit-identical outputs."""
    row = neighbor_rows(graph, level, c)  # (cap,)

    if strategy == "plain":
        # HNSW scans the complete neighbor list (degree already bounded by
        # construction); no predicate, no truncation.
        return row

    pm = None if pass_mask is None else pass_mask[None]
    vis = None if visited is None else visited[None]
    out = neighbor_expand(row[None], graph.neighbors[level], graph.pos[level],
                          pm, vis, strategy=strategy, m=m, m_beta=m_beta,
                          use_kernel=use_kernel, interpret=interpret)
    return out[0]


def _strategy_for(variant: str, level: int, compressed_level0: bool) -> str:
    if variant == "hnsw":
        return "plain"
    if variant == "acorn-1":
        return "two_hop"
    if variant == "acorn-gamma":
        if level == 0 and compressed_level0:
            return "compress"
        return "filter"
    raise ValueError(variant)


def _batched_neighbors(graph, level, cs, pass_mask, strategy, m, m_beta,
                       visited=None, use_kernel=False, interpret=False):
    """get_neighbors over the query batch: (B,) ids -> (B, M).

    Natively batched (no vmap): the whole batch's expansions issue as one
    ``neighbor_expand`` call — one Pallas launch with a (B,) grid when
    ``use_kernel=True``."""
    rows = neighbor_rows(graph, level, cs)  # (B, cap)
    if strategy == "plain":
        return rows
    return neighbor_expand(rows, graph.neighbors[level], graph.pos[level],
                           pass_mask, visited, strategy=strategy, m=m,
                           m_beta=m_beta, use_kernel=use_kernel,
                           interpret=interpret)


# ---------------------------------------------------------------------------
# the search itself
# ---------------------------------------------------------------------------


def _batch_dists(x: Array, ids: Array, xq: Array, metric: str,
                 use_kernel: bool, interpret: bool) -> Array:
    """Distances from each query to its gathered neighbor rows.

    ids (B, M) int32 (-1 padded), xq (B, d) -> (B, M); INVALID ids -> +inf.
    The single point where the search pipeline touches vector data: routed
    through the gather_distance Pallas kernel or its jnp reference.
    """
    if use_kernel:
        return gather_distance(ids, xq, x, metric=metric, use_kernel=True,
                               interpret=interpret)
    return gather_distance_ref(ids, xq, x, metric)


def _greedy_level(graph, x, level, e, ed, xq, metric, max_steps, dc,
                  use_kernel, interpret):
    """Batched ef=1 greedy descent at one level (Algorithm 1 upper levels)
    over the full neighbor lists, with no predicate.

    e (B,) current nodes, ed (B,) their distances; lanes freeze once their
    own step stops improving (vmap-of-while_loop carry contract)."""

    def lane_cond(state):
        _, _, moved, it, _ = state
        return moved & (it < max_steps)

    def cond(state):
        return lane_cond(state).any()

    def body(state):
        e, ed, moved, it, dc = state
        active = lane_cond(state)
        nbrs = neighbor_rows(graph, level, e)
        d = _batch_dists(x, nbrs, xq, metric, use_kernel, interpret)
        dc2 = dc + jnp.sum(nbrs >= 0, axis=1, dtype=jnp.int32)
        j = jnp.argmin(d, axis=1)
        dj = jnp.take_along_axis(d, j[:, None], axis=1)[:, 0]
        nj = jnp.take_along_axis(nbrs, j[:, None], axis=1)[:, 0]
        better = dj < ed
        new_state = (jnp.where(better, nj, e), jnp.where(better, dj, ed),
                     better, it + 1, dc2)
        return tuple(jnp.where(_lanes(active, nw.ndim), nw, od)
                     for nw, od in zip(new_state, state))

    b = e.shape[0]
    state = (e, ed, jnp.ones((b,), bool), jnp.zeros((b,), jnp.int32), dc)
    e, ed, _, _, dc = jax.lax.while_loop(cond, body, state)
    return e, ed, dc


def _search_impl(
    graph: LayeredGraph,
    x: Array,
    xq: Array,
    pass_mask: Optional[Array],
    k: int,
    ef: int,
    variant: str,
    m: int,
    m_beta: int,
    metric: str,
    compressed_level0: bool,
    max_expansions: int,
    spec: ExecutionSpec = ExecutionSpec(),
) -> Tuple[Array, Array, SearchStats]:
    """Batched hybrid search: xq (B, d), pass_mask (B, n) or None.

    ``spec`` carries the kernel-routing knobs (``use_kernel``/
    ``interpret``/``expand_kernel``; an unresolved ``expand_kernel`` of
    ``None`` follows ``use_kernel`` — one switch flips the whole
    kernel-fused pipeline).  The mesh fields are dispatch-layer policy
    and are ignored here."""
    use_kernel, interpret = spec.use_kernel, spec.interpret
    expand_kernel = spec.resolved_expand_kernel()
    b = xq.shape[0]
    n = x.shape[0]
    top = graph.num_levels - 1
    rows = jnp.arange(b)
    # the expansion kernel reads the predicate as a packed bitmap: pack it
    # once per batch, not once per expansion
    kmask = (pack_bitmap(pass_mask)
             if expand_kernel and pass_mask is not None else pass_mask)
    e = jnp.broadcast_to(graph.entry_point, (b,)).astype(jnp.int32)
    ed = _batch_dists(x, e[:, None], xq, metric, use_kernel, interpret)[:, 0]
    dc = jnp.ones((b,), jnp.int32)

    # ---- stage 1 + upper levels: greedy descent (Algorithm 1) ----
    # Predicate-agnostic (beyond-paper: the paper walks the predicate
    # subgraph here).  The descent only picks the level-0 entry, and a
    # filtered one lands on the nearest *passing* upper-level node: when
    # the query's region holds none (a sparse upper level, a selective or
    # clustered predicate), it lands in another region whose level-0
    # lists never reach the query's, and recall plateaus whatever ef is.
    # Walking the full upper-level lists lands next to the query; the
    # level-0 beam and the seeds below apply the predicate.
    for lvl in range(top, 0, -1):
        e, ed, dc = _greedy_level(graph, x, lvl, e, ed, xq, metric, 128, dc,
                                  use_kernel, interpret)

    # ---- level 0: beam search (Algorithm 2) ----
    strat0 = _strategy_for(variant, 0, compressed_level0)
    e_safe = jnp.clip(e, 0, n - 1)
    beam_ids = jnp.full((b, ef), INVALID, jnp.int32).at[:, 0].set(e)
    beam_d = jnp.full((b, ef), INF).at[:, 0].set(ed)
    beam_exp = jnp.zeros((b, ef), bool)
    if pass_mask is None:
        e_pass = jnp.ones((b,), bool)
    else:
        e_pass = (jnp.take_along_axis(pass_mask, e_safe[:, None], axis=1)[:, 0]
                  & (e >= 0))
    beam_pass = jnp.zeros((b, ef), bool).at[:, 0].set(e_pass)
    visited = jnp.zeros((b, n), bool).at[rows, e_safe].set(True)

    # Multi-seed (beyond-paper, EXPERIMENTS.md §Repro-notes): when the
    # predicate-passing set is multi-region, a single entry confines the
    # beam to one region.  The γ-dense level-1 neighborhood of the landing
    # point spans regions, so its predicate-passing members seed the beam
    # too (costing the same ≤m distance computations the descent's last
    # step already paid in spirit; ef must simply be > m).
    if pass_mask is not None and graph.num_levels > 1 and ef > m:
        strat1 = _strategy_for(variant, 1, compressed_level0)
        seeds = _batched_neighbors(graph, 1, e, kmask, strat1, m, m_beta,
                                   use_kernel=expand_kernel,
                                   interpret=interpret)
        seeds = seeds[:, :m]  # 'plain' rows may be wider than m
        s = seeds.shape[1]
        sd = _batch_dists(x, seeds, xq, metric, use_kernel, interpret)
        dc = dc + jnp.sum(seeds >= 0, axis=1, dtype=jnp.int32)
        dup = seeds == e[:, None]
        sd = jnp.where(dup, INF, sd)
        beam_ids = beam_ids.at[:, 1:s + 1].set(jnp.where(dup, INVALID, seeds))
        beam_d = beam_d.at[:, 1:s + 1].set(sd)
        beam_pass = beam_pass.at[:, 1:s + 1].set((seeds >= 0) & ~dup)
        visited = visited.at[rows[:, None],
                             jnp.clip(seeds, 0, n - 1)].max(seeds >= 0)

    # the bounded sorted-merge maintains a sorted beam; establish the
    # invariant once (stable: ties keep insertion order, matching argsort)
    order0 = jnp.argsort(beam_d, axis=1, stable=True)
    beam_ids = jnp.take_along_axis(beam_ids, order0, axis=1)
    beam_d = jnp.take_along_axis(beam_d, order0, axis=1)
    beam_pass = jnp.take_along_axis(beam_pass, order0, axis=1)

    def lane_cond(state):
        beam_ids, beam_d, beam_exp, _, _, it, _ = state
        unexp = (beam_ids >= 0) & ~beam_exp
        any_unexp = unexp.any(axis=1)
        best_unexp = jnp.where(unexp, beam_d, INF).min(axis=1)
        full = (beam_ids >= 0).all(axis=1)
        worst = jnp.where(full, beam_d.max(axis=1), INF)
        return any_unexp & (best_unexp <= worst) & (it < max_expansions)

    def cond(state):
        return lane_cond(state).any()

    def body(state):
        beam_ids, beam_d, beam_exp, beam_pass, visited, it, dc = state
        active = lane_cond(state)  # per-lane no-op guard for frozen lanes
        unexp = (beam_ids >= 0) & ~beam_exp
        sel = jnp.argmin(jnp.where(unexp, beam_d, INF), axis=1)
        c = jnp.take_along_axis(beam_ids, sel[:, None], axis=1)[:, 0]
        beam_exp2 = beam_exp.at[rows, sel].set(True)

        if packed_visited:
            # the kernel skips visited ids and sets the bits of those it
            # returns, in place: every returned id is fresh
            nbrs, visited2 = neighbor_expand_packed(
                neighbor_rows(graph, 0, c), graph.neighbors[0],
                graph.pos[0], kmask, visited, strategy=strat0, m=m,
                m_beta=m_beta, interpret=interpret)
            fresh = nbrs >= 0
        else:
            nbrs = _batched_neighbors(graph, 0, c, pass_mask, strat0, m,
                                      m_beta, visited=visited,
                                      use_kernel=expand_kernel,
                                      interpret=interpret)
            safe = jnp.clip(nbrs, 0, n - 1)
            fresh = (nbrs >= 0) & ~jnp.take_along_axis(visited, safe, axis=1)
            visited2 = visited.at[rows[:, None], safe].max(nbrs >= 0)
        nd = jnp.where(fresh,
                       _batch_dists(x, nbrs, xq, metric, use_kernel,
                                    interpret), INF)
        dc2 = dc + jnp.sum(fresh, axis=1, dtype=jnp.int32)

        # bounded sorted-merge into the beam: O((ef+M) log M), not a full
        # (ef+M) argsort — beam is sorted, only the M candidates are not
        cand_ids = jnp.where(fresh, nbrs, INVALID)
        merged_d, (m_ids, m_exp, m_pass) = bounded_sorted_merge(
            beam_d, nd,
            (beam_ids, beam_exp2, beam_pass),
            (cand_ids, jnp.zeros_like(fresh), fresh))
        new_state = (m_ids, merged_d, m_exp, m_pass, visited2, it + 1, dc2)
        return tuple(jnp.where(_lanes(active, nw.ndim), nw, od)
                     for nw, od in zip(new_state, state))

    packed_visited = expand_kernel and strat0 != "plain"
    if packed_visited:
        visited = pack_bitmap(visited)
    state = (beam_ids, beam_d, beam_exp, beam_pass, visited,
             jnp.zeros((b,), jnp.int32), dc)
    beam_ids, beam_d, beam_exp, beam_pass, visited, hops, dc = (
        jax.lax.while_loop(cond, body, state)
    )

    # final top-k among predicate-passing beam entries
    final_d = jnp.where(beam_pass & (beam_ids >= 0), beam_d, INF)
    order = jnp.argsort(final_d, axis=1, stable=True)[:, :k]
    out_d = jnp.take_along_axis(final_d, order, axis=1)
    out_ids = jnp.where(jnp.isfinite(out_d),
                        jnp.take_along_axis(beam_ids, order, axis=1), INVALID)
    return out_ids, out_d, SearchStats(dist_comps=dc, hops=hops)


@functools.partial(
    jax.jit,
    static_argnames=("k", "ef", "variant", "m", "m_beta", "metric",
                     "compressed_level0", "max_expansions", "spec"),
)
def _hybrid_search_jit(graph, x, xq, pass_mask, k, ef, variant, m, m_beta,
                       metric, compressed_level0, max_expansions, spec):
    return _search_impl(
        graph, x, xq, pass_mask, k, ef, variant, m, m_beta, metric,
        compressed_level0, max_expansions, spec)


def hybrid_search(
    graph: LayeredGraph,
    x: Array,
    xq: Array,
    pass_mask: Array,
    k: int = 10,
    ef: int = 64,
    variant: str = "acorn-gamma",
    m: int = 16,
    m_beta: int = 32,
    metric: str = "l2",
    compressed_level0: bool = True,
    max_expansions: int = 512,
    spec: Optional[ExecutionSpec] = None,
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
    expand_kernel: Optional[bool] = None,
):
    """Batched hybrid search.

    xq: (B, d) queries; pass_mask: (B, n) predicate masks.
    Execution knobs ride in ``spec`` (:class:`repro.core.plan.
    ExecutionSpec`): ``spec.use_kernel`` routes distance computations
    through the gather_distance Pallas kernel and (by default) neighbor
    expansion through the neighbor_expand kernel (``spec.interpret=True``
    for CPU execution; compiled on TPU); the default spec is the pure-jnp
    reference path — both return identical neighbor ids.
    The retired ``use_kernel``/``interpret``/``expand_kernel`` kwargs
    raise ``TypeError`` with the matching ``ExecutionSpec`` field.
    Returns ids (B, k), dists (B, k), SearchStats with (B,) fields.
    """
    spec = resolve_execution_spec(
        spec, "hybrid_search", use_kernel=use_kernel, interpret=interpret,
        expand_kernel=expand_kernel)
    # mesh fields pinned: this is the single-device entry point, so specs
    # differing only in dispatch-layer mesh shape share one trace
    return _hybrid_search_jit(graph, x, xq, pass_mask, k, ef, variant, m,
                              m_beta, metric, compressed_level0,
                              max_expansions,
                              spec.resolve(data_parallel=1,
                                           corpus_parallel=1))


# mesh-aware variants: one jitted shard_map callable per (mesh, config)
_SHARDED_FNS: dict = {}


def hybrid_search_sharded(
    graph: LayeredGraph,
    x: Array,
    xq: Array,
    pass_mask: Optional[Array],
    data_parallel: Optional[int] = None,
    k: int = 10,
    ef: int = 64,
    variant: str = "acorn-gamma",
    m: int = 16,
    m_beta: int = 32,
    metric: str = "l2",
    compressed_level0: bool = True,
    max_expansions: int = 512,
    spec: Optional[ExecutionSpec] = None,
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
    expand_kernel: Optional[bool] = None,
):
    """Mesh-aware :func:`hybrid_search`: queries sharded across devices.

    Shards ``xq``/``pass_mask`` over a 1-D ``data`` mesh of local devices
    with the graph and vectors replicated, via
    ``repro.distributed.query_parallel``.  The mesh size comes from
    ``spec.data_parallel`` (``None``/``0`` -> all local devices; clamped
    to the host's count).  NOTE: with no ``spec`` at all this entry
    point's historical default is ALL local devices, but an explicit
    ``spec=ExecutionSpec()`` means what it says — ``data_parallel=1``,
    single device; pass ``ExecutionSpec(data_parallel=0)`` to shard over
    every local device.  The retired positional ``data_parallel`` arg and
    kernel knob kwargs raise ``TypeError`` naming the ``ExecutionSpec``
    field.  ``xq`` is padded up to a mesh
    multiple (padding lanes discarded), and results are bit-identical to
    the single-device path.  ``pass_mask=None`` runs the unfiltered
    plain-HNSW substrate, as in :func:`repro.core.batched.search_batch`.
    """
    from repro.distributed.query_parallel import (pad_to_multiple,
                                                  resolve_data_parallel,
                                                  sharded_search_fn)
    spec_given = spec is not None
    spec = resolve_execution_spec(
        spec, "hybrid_search_sharded", use_kernel=use_kernel,
        interpret=interpret, expand_kernel=expand_kernel,
        data_parallel=data_parallel)
    if not spec_given:
        # historical default of this entry point: all local devices
        spec = spec.overlay(data_parallel=0)
    if pass_mask is None:
        variant, compressed_level0 = "hnsw", False
    dp = resolve_data_parallel(spec.data_parallel)
    local_spec = spec.resolve(data_parallel=dp, corpus_parallel=1)
    statics = dict(k=k, ef=ef, variant=variant, m=m, m_beta=m_beta,
                   metric=metric, compressed_level0=compressed_level0,
                   max_expansions=max_expansions, spec=local_spec)
    b = xq.shape[0]
    if dp <= 1 or b == 0:
        return hybrid_search(graph, x, xq, pass_mask, spec=local_spec,
                             **{k_: v for k_, v in statics.items()
                                if k_ != "spec"})
    key = (dp, pass_mask is not None, tuple(sorted(
        (k_, v) for k_, v in statics.items())))
    fn = _SHARDED_FNS.get(key)
    if fn is None:
        fn = _SHARDED_FNS[key] = jax.jit(
            sharded_search_fn(dp, pass_mask is not None, statics))
    pb = pad_to_multiple(b, dp)
    if pb != b:
        from repro.core.batched import pad_rows
        xq = pad_rows(xq, pb - b)
        if pass_mask is not None:
            pass_mask = pad_rows(pass_mask, pb - b)
    ids, d, st = fn(graph, x, xq, pass_mask)
    return ids[:b], d[:b], SearchStats(dist_comps=st.dist_comps[:b],
                                       hops=st.hops[:b])


def ann_search(
    graph: LayeredGraph,
    x: Array,
    xq: Array,
    k: int = 10,
    ef: int = 64,
    m: int = 32,
    metric: str = "l2",
    max_expansions: int = 512,
    spec: Optional[ExecutionSpec] = None,
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
):
    """Plain (unfiltered) HNSW ANN search — baseline substrate.

    Execution knobs ride in ``spec``; the retired ``use_kernel``/
    ``interpret`` kwargs raise ``TypeError``."""
    spec = resolve_execution_spec(
        spec, "ann_search", use_kernel=use_kernel, interpret=interpret)
    return _hybrid_search_jit(graph, x, xq, None, k, ef, "hnsw", m, 0,
                              metric, False, max_expansions,
                              spec.resolve(data_parallel=1,
                                           corpus_parallel=1))
