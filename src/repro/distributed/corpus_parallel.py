"""Mesh-native corpus sharding: SPMD per-shard search + collective merge.

The serving engine shards its corpus row-wise into per-shard ACORN indexes;
until this module those shards were walked in a host-side Python loop and
merged with ``jnp.concatenate``.  Here the whole fan-out runs as ONE SPMD
program on a 2-D ``(data, corpus)`` mesh:

  * the corpus is sharded along ``corpus`` — per-shard vectors, graph
    neighbor tables, AND the packed attribute columns are stacked on a
    leading shard axis (:class:`ShardedCorpus`, shapes padded to a common
    envelope so every shard is one slice of the same arrays) and split one
    shard per corpus-mesh device;
  * queries are sharded along ``data`` and replicated along ``corpus`` —
    every corpus shard answers every query, split across data devices for
    throughput (the same query-parallel win ``query_parallel`` buys);
  * predicates arrive as a compiled :class:`repro.core.plan.
    PredicateProgram` — per-query instruction rows sharded along ``data``
    like the queries — plus per-shard ``aux`` regex-leaf bitmaps sharded
    along ``corpus``.  Each device evaluates its own shard's pass-masks
    IN-PROGRAM against its shard-resident columns
    (:func:`repro.core.plan.evaluate_program`), so the host never
    materializes or transfers a ``(B, n_shard)`` mask per shard — queries
    carry compiled predicate operands, not masks.  This is the
    predicate-inside-the-plan placement NaviX / the GPU all-in-one index
    argue for, and the prerequisite for multi-host serving where a host
    ``(B, n_total)`` mask cannot exist;
  * each device runs the batched ACORN search (``core.search._search_impl``)
    on its local shard, converts local row ids to global ids with its
    shard's base offset, and the cross-shard top-k merge is a native
    collective: all-gather of k candidates per shard + the deterministic
    (distance, global-id) lexsort merge
    (:func:`repro.distributed.collectives.gathered_topk_merge`).

Shape-padding parity: stacking pads each shard's graph to the max level
count / row count / neighbor cap across shards with ``-1`` (and vectors
with zero rows).  Padded levels have an all ``-1`` ``pos`` table, so every
lookup degrades to an empty neighbor row and the greedy descent freezes
immediately without a distance computation; padded rows never appear in
any neighbor table, so they are never visited or scored.  Padded
*attribute* rows are zero-filled and could spuriously satisfy a predicate
(label 0 is a real value), so the in-program evaluation masks rows
``>= n_rows`` to False — exactly the zero-initialized tail the host-side
mask embedding used to produce.  Per-shard results are therefore
bit-identical to searching the shard's own unpadded graph (asserted
directly in tests/test_corpus_parallel.py).

Fault injection and routing ride in as data, not control flow: an
``alive`` (S,) mask zeroes a failed shard's candidates before the merge
(the host loop's "shard contributes nothing" semantics), and per-(shard,
query) pre-filter routing decisions select host-computed exact brute-force
results over the graph search inside the kernel, keeping ACORN's §5.2
cost-based router bit-identical to the host path.

Execution policy is ONE resolved :class:`repro.core.plan.ExecutionSpec`
(``data_parallel`` × ``corpus_parallel`` = the mesh shape); it terminates
every variant-cache key as ``(..., program_shape_sig, spec, "corpus")``.

Local testing recipe (XLA fixes the host device count at first init):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m pytest tests/test_corpus_parallel.py
"""
from __future__ import annotations

from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.compat import shard_map
from repro.core.batched import VariantCache, pad_rows, plan_chunks
from repro.core.graph import INVALID, LayeredGraph
from repro.core.plan import (ExecutionSpec, PackedColumns, PredicateProgram,
                             TableSchema, evaluate_program, pack_columns,
                             regex_aux)
from repro.core.search import _search_impl

from .collectives import gathered_topk_merge
from .query_parallel import local_device_count

Array = jax.Array

# mesh cache: identity matters for jit cache hits (see query_parallel)
_MESHES: Dict[tuple, Mesh] = {}


class ShardedCorpus(NamedTuple):
    """Row-sharded corpus stacked on a leading shard axis (a pytree).

    Every leaf carries the shard axis first, so a single ``P("corpus")``
    prefix spec splits the whole structure one shard per corpus device.
    ``columns`` holds the shard-resident packed attribute columns
    (``ints (S, C_int, n_max)``, ``bitsets (S, C_bit, n_max, W)``) the
    SPMD kernel evaluates compiled predicate programs against; ``None``
    when the corpus was stacked without tables (graph-only parity
    harnesses) — such a corpus cannot serve predicate programs.
    """

    graph: LayeredGraph  # every leaf stacked: (S, ...)
    x: Array             # (S, n_max, d) vectors, zero-padded rows
    bases: Array         # (S,) int32 global row offset per shard
    n_rows: Array        # (S,) int32 valid rows per shard
    columns: Optional[PackedColumns] = None  # stacked: leaves (S, ...)

    @property
    def n_shards(self) -> int:
        return int(self.bases.shape[0])


def stack_corpus(graphs: Sequence[LayeredGraph], xs: Sequence[Array],
                 bases: Sequence[int],
                 tables: Optional[Sequence] = None) -> ShardedCorpus:
    """Stack per-shard graphs/vectors (and attribute tables) into one
    :class:`ShardedCorpus`.

    Shards are padded to a common envelope: max level count, per-level max
    row count and neighbor cap (``-1`` filled), max corpus rows (zero-filled
    vectors, ``-1`` ``pos``, zero-filled attribute columns).  Padding is
    invisible to the search — see the module docstring for the parity
    argument.  ``tables`` (per-shard ``AttributeTable``s sharing one
    schema) populates ``columns`` so predicate programs evaluate on
    device, next to each shard's rows.
    """
    s_count = len(graphs)
    assert s_count == len(xs) == len(bases)
    num_levels = max(g.num_levels for g in graphs)
    n_max = max(int(x.shape[0]) for x in xs)
    dim = int(xs[0].shape[1])

    xs_np = [np.asarray(x) for x in xs]
    x_stack = np.zeros((s_count, n_max, dim), xs_np[0].dtype)
    for s, x in enumerate(xs_np):
        x_stack[s, : x.shape[0]] = x

    neighbors: List[Array] = []
    pos: List[Array] = []
    node_ids: List[Array] = []
    for lvl in range(num_levels):
        have = [g for g in graphs if lvl < g.num_levels]
        rows = max(1, max(int(g.neighbors[lvl].shape[0]) for g in have))
        cap = max(1, max(int(g.neighbors[lvl].shape[1]) for g in have))
        nb = np.full((s_count, rows, cap), INVALID, np.int32)
        po = np.full((s_count, n_max), INVALID, np.int32)
        ni = np.full((s_count, rows), INVALID, np.int32)
        for s, g in enumerate(graphs):
            if lvl >= g.num_levels:
                continue  # all -1: the level is empty for this shard
            a = np.asarray(g.neighbors[lvl])
            nb[s, : a.shape[0], : a.shape[1]] = a
            p = np.asarray(g.pos[lvl])
            po[s, : p.shape[0]] = p
            i = np.asarray(g.node_ids[lvl])
            ni[s, : i.shape[0]] = i
        neighbors.append(jnp.asarray(nb))
        pos.append(jnp.asarray(po))
        node_ids.append(jnp.asarray(ni))

    levels = np.zeros((s_count, n_max), np.int32)
    for s, g in enumerate(graphs):
        lv = np.asarray(g.levels)
        levels[s, : lv.shape[0]] = lv
    graph = LayeredGraph(
        neighbors=tuple(neighbors), pos=tuple(pos), node_ids=tuple(node_ids),
        entry_point=jnp.asarray(
            np.array([int(g.entry_point) for g in graphs], np.int32)),
        levels=jnp.asarray(levels))

    columns = None
    if tables is not None:
        assert len(tables) == s_count
        schema = TableSchema.of(tables[0])
        for s, t in enumerate(tables[1:], start=1):
            if TableSchema.of(t) != schema:
                # slot lookups are positional: a shard with different
                # columns (or a different dict order) would silently pack
                # into the wrong slots and bend every compiled program
                raise ValueError(
                    f"shard {s} table schema {TableSchema.of(t)} != shard "
                    f"0 schema {schema} — corpus shards must share one "
                    "column layout")
        per = [pack_columns(t, schema) for t in tables]
        ci = per[0].ints.shape[0]
        cb, w = per[0].bitsets.shape[0], per[0].bitsets.shape[2]
        ints = np.zeros((s_count, ci, n_max), np.int32)
        bitsets = np.zeros((s_count, cb, n_max, w), np.uint32)
        for s, pc in enumerate(per):
            n_s = pc.ints.shape[1]
            ints[s, :, :n_s] = np.asarray(pc.ints)
            bitsets[s, :, :n_s] = np.asarray(pc.bitsets)
        columns = PackedColumns(ints=jnp.asarray(ints),
                                bitsets=jnp.asarray(bitsets))
    return ShardedCorpus(
        graph=graph, x=jnp.asarray(x_stack),
        bases=jnp.asarray(np.asarray(list(bases), np.int32)),
        n_rows=jnp.asarray(np.array([x.shape[0] for x in xs_np], np.int32)),
        columns=columns)


def stack_regex_aux(tables: Sequence, n_max: int,
                    regex_leaves: Tuple[Tuple[str, str], ...]) -> Array:
    """Per-shard host-evaluated regex-leaf bitmaps, stacked (S, A, n_max).

    Rows pad with False beyond each shard's length; served from each
    table's ``(column, pattern)`` cache, so a repeated pattern costs one
    string-column scan per shard total, not one per batch.
    """
    s_count = len(tables)
    a = max(1, len(regex_leaves))
    out = np.zeros((s_count, a, n_max), bool)
    for s, t in enumerate(tables):
        block = np.asarray(regex_aux(t, regex_leaves))
        out[s, : block.shape[0], : block.shape[1]] = block
    return jnp.asarray(out)


def shard_slice(corpus: ShardedCorpus, s: int) -> Tuple[LayeredGraph, Array]:
    """Host-side view of shard ``s``'s (padded) graph and vectors — the
    exact arrays the SPMD kernel sees on corpus device ``s``."""
    graph = jax.tree_util.tree_map(lambda a: a[s], corpus.graph)
    return graph, corpus.x[s]


# ---------------------------------------------------------------------------
# mesh construction
# ---------------------------------------------------------------------------


def corpus_mesh(dp: int, cp: int) -> Mesh:
    """A 2-D ``(data, corpus)`` mesh over the first ``dp * cp`` local
    devices; cached so repeated requests share identity (jit cache hits)."""
    ndev = dp * cp
    devs = jax.local_devices()[:ndev]
    if len(devs) < ndev:
        raise ValueError(
            f"(data={dp}) x (corpus={cp}) mesh needs {ndev} devices but "
            f"only {len(devs)} are local")
    key = (dp, cp, tuple(d.id for d in devs))
    mesh = _MESHES.get(key)
    if mesh is None:
        mesh = _MESHES[key] = Mesh(
            np.asarray(devs).reshape(dp, cp), ("data", "corpus"))
    return mesh


def place_corpus(corpus: ShardedCorpus, dp: int, cp: int) -> ShardedCorpus:
    """Put shard ``s`` of every corpus array on corpus device ``s`` of the
    ``(dp, cp)`` mesh (replicated along ``data``) — the placement the SPMD
    program's in_specs name, so a batch moves no corpus bytes."""
    return jax.device_put(corpus,
                          NamedSharding(corpus_mesh(dp, cp), P("corpus")))


def resolve_corpus_mesh_shape(
    n_shards: int,
    data_parallel: Optional[int] = None,
    corpus_parallel: Optional[int] = None,
) -> Optional[Tuple[int, int]]:
    """Pick the ``(data, corpus)`` mesh shape for an ``n_shards`` corpus.

    The corpus axis holds exactly one shard per device, so its size is
    pinned to ``n_shards``; an explicit ``corpus_parallel`` naming any
    other value raises.  ``corpus_parallel=None``/``0`` means *auto*: use
    the SPMD path whenever the host has at least ``n_shards`` devices and
    the corpus is actually sharded (``n_shards > 1``); pass
    ``corpus_parallel == n_shards`` explicitly to request SPMD even for a
    single shard (e.g. an 8x1 pure query-parallel mesh).  The data axis
    takes ``data_parallel`` clamped to the leftover device budget
    (``None``/``0`` = all of it).  In auto mode, returns ``None`` when the
    host cannot fit the mesh — callers serve through the host loop and
    count it (``ServingEngine.stats["host_loop_batches"]``).  An explicit
    ``corpus_parallel`` that the host cannot fit raises instead: a caller
    that asked for the mesh never gets the host loop in its place.
    """
    auto = corpus_parallel in (None, 0)
    if not auto and int(corpus_parallel) != n_shards:
        raise ValueError(
            f"corpus_parallel={corpus_parallel} but the corpus has "
            f"{n_shards} shards — the corpus mesh axis holds exactly one "
            "shard per device")
    if auto and n_shards <= 1:
        return None
    cp = n_shards
    ndev = local_device_count()
    if ndev < cp:
        if auto:
            return None
        raise ValueError(
            f"corpus_parallel={cp} asks for one device per corpus shard "
            f"but only {ndev} devices are local")
    budget = ndev // cp
    if not data_parallel:  # None / 0 -> all leftover devices
        dp = budget
    else:
        dp = max(1, min(int(data_parallel), budget))
    return dp, cp


# ---------------------------------------------------------------------------
# the SPMD kernel
# ---------------------------------------------------------------------------


def corpus_search_fn(dp: int, cp: int, statics: dict) -> Callable:
    """Build the shard_map'd corpus-sharded search for one compiled variant.

    Returns ``f(corpus, xq, program, aux, pre_ids, pre_d, use_pre, alive)``
    where

      * ``corpus``  — :class:`ShardedCorpus` (with ``columns``), split
        along ``corpus``;
      * ``xq``      — (B, d) queries, split along ``data``, replicated
        along ``corpus``;
      * ``program`` — :class:`PredicateProgram`, per-query instruction
        rows split along ``data`` like the queries (operands, not masks);
      * ``aux``     — (S, A, n_max) host-evaluated regex-leaf bitmaps,
        split along ``corpus``;
      * ``pre_ids``/``pre_d`` — (S, B, k) host-computed exact pre-filter
        results for the (shard, query) pairs routed off the graph;
      * ``use_pre`` — (S, B) bool per-(shard, query) route decisions;
      * ``alive``   — (S,) bool; a dead shard contributes no candidates.

    Each device first evaluates its shard's pass-masks in-program
    (``evaluate_program`` over the shard-resident columns, padded rows
    forced False), then searches — the ``(B, n_shard)`` mask exists only
    device-side, per shard, inside the fused program.

    Output: merged global ids/dists (B, k) plus per-shard (S, B)
    dist_comps/hops for observability.  ``B`` must be a multiple of
    ``dp``.  Wrap in ``jax.jit`` (the variant cache does).

    The merged result is computed identically on every corpus device (the
    all-gather hands each the full candidate set), but the out_specs do
    NOT leave the ``corpus`` axis unmentioned: with the replication check
    off, how GSPMD assembles an unmentioned output axis is unspecified —
    it can compile to a cross-replica SUM depending on input-sharding
    context (observed: ids/dists exactly x ``cp``).  Instead each device
    emits its copy under an explicit leading ``corpus`` dim (S, B, k) and
    the caller slices copy 0 — exact, because the copies are identical.
    """
    mesh = corpus_mesh(dp, cp)
    k = statics["k"]
    cspec = P("corpus")
    dspec = P("data")
    sq = P("corpus", "data")

    def local(corpus, xq, program, aux, pre_ids, pre_d, use_pre, alive):
        graph = jax.tree_util.tree_map(lambda a: a[0], corpus.graph)
        # in-program predicate evaluation against shard-resident columns;
        # envelope-padded rows (>= n_rows) forced False — bit-identical to
        # the host-embedded mask tail the legacy path produced
        mask = evaluate_program(program, corpus.columns.ints[0],
                                corpus.columns.bitsets[0], aux[0],
                                n_valid=corpus.n_rows[0])
        ids, d, st = _search_impl(graph, corpus.x[0], xq, mask, **statics)
        # §5.2 routing: low-selectivity (shard, query) pairs take the exact
        # pre-filter answer computed host-side; the graph lanes they rode
        # are fixed-shape padding and get discarded here
        route_pre = use_pre[0][:, None]
        ids = jnp.where(route_pre, pre_ids[0], ids)
        d = jnp.where(route_pre, pre_d[0], d)
        # local-id -> global-id offset; dead shards contribute nothing
        gids = jnp.where((ids >= 0) & alive[0], ids + corpus.bases[0],
                         INVALID)
        d = jnp.where(gids >= 0, d, jnp.inf)
        out_ids, out_d = gathered_topk_merge(gids, d, k, axis="corpus")
        return (out_ids[None], out_d[None],
                st.dist_comps[None], st.hops[None])

    f = shard_map(
        local, mesh,
        in_specs=(cspec, dspec, dspec, cspec, sq, sq, sq, cspec),
        out_specs=(sq, sq, sq, sq), check_vma=False)

    def apply(corpus, xq, program, aux, pre_ids, pre_d, use_pre, alive):
        ids, d, dcs, hps = f(corpus, xq, program, aux, pre_ids, pre_d,
                             use_pre, alive)
        return ids[0], d[0], dcs, hps

    return apply


def _pad_queries(a: Array, pad: int) -> Array:
    """Pad the query axis (axis 1) of a per-shard array by repeating the
    last query's entry (discarded after the bucketed dispatch)."""
    tail = jnp.broadcast_to(a[:, -1:], (a.shape[0], pad) + a.shape[2:])
    return jnp.concatenate([a, tail], axis=1)


def _build_corpus_variant(cache: VariantCache, key: tuple, statics: dict,
                          dp: int, cp: int) -> Callable:
    impl = corpus_search_fn(dp, cp, statics)

    def fn(corpus, xq, program, aux, pre_ids, pre_d, use_pre, alive):
        # runs only while tracing -> counts real (re)compilations
        cache.trace_counts[key] = cache.trace_counts.get(key, 0) + 1
        return impl(corpus, xq, program, aux, pre_ids, pre_d, use_pre, alive)

    return jax.jit(fn)


def corpus_search_batch(
    corpus: ShardedCorpus,
    xq: Array,
    program: PredicateProgram,
    aux: Array,
    pre_ids: Array,
    pre_d: Array,
    use_pre: Array,
    alive: Array,
    *,
    k: int,
    ef: int,
    variant: str,
    m: int,
    m_beta: int,
    metric: str,
    compressed_level0: bool,
    max_expansions: int,
    spec: ExecutionSpec,
    buckets: Tuple[int, ...],
    cache: VariantCache,
) -> Tuple[Array, Array, Array, Array]:
    """Ragged-batch corpus-sharded SPMD search through jit buckets.

    The corpus-sharded sibling of ``repro.core.batched.search_batch``:
    queries (and the program's per-query instruction rows) are planned
    into mesh-multiple jit buckets
    (``plan_chunks(multiple_of=spec.data_parallel)``) and dispatched
    through ``cache`` — keys end with ``(program_shape_sig, spec,
    "corpus")``, the resolved :class:`ExecutionSpec` carrying the mesh
    shape, so a steady-state server runs one trace per (bucket, config,
    program-shape, mesh) tuple.  Returns merged global ids (B, k), dists
    (B, k), and per-shard dist_comps/hops (S, B).

    Each chunk's outputs are materialized to host before use: the jitted
    mesh program's outputs carry a GSPMD sharding that marks the merged
    result replicated along ``corpus`` (``last_tile_dim_replicate``), and
    on this jax/XLA feeding such an array into a *further* traced op
    (e.g. ``jnp.concatenate`` over serve() batches) can compile into a
    cross-replica SUM — ids/dists come back exactly x n_shards (observed
    on the CPU backend; compile-context dependent, so a parity test can
    pass while a differently-ordered run corrupts).  Fetching through the
    host reads one replica and ends the mesh computation at the dispatch
    boundary, which is where serving results leave the device anyway;
    the arrays are k-small.
    """
    spec = spec.resolve()
    dp, cp = spec.data_parallel, spec.corpus_parallel
    if not isinstance(dp, int) or not isinstance(cp, int) or dp < 1:
        raise ValueError(
            f"corpus_search_batch needs a resolved mesh spec, got {spec}")
    if corpus.n_shards != cp:
        raise ValueError(
            f"corpus has {corpus.n_shards} shards but corpus_parallel={cp}")
    if corpus.columns is None:
        raise ValueError(
            "corpus was stacked without attribute tables — in-program "
            "predicate evaluation needs shard-resident columns "
            "(stack_corpus(..., tables=...))")
    statics = dict(k=k, ef=ef, variant=variant, m=m, m_beta=m_beta,
                   metric=metric, compressed_level0=compressed_level0,
                   max_expansions=max_expansions, spec=spec)
    total = xq.shape[0]
    if total == 0:  # mirror search_batch's empty-batch contract
        z = jnp.zeros((corpus.n_shards, 0), jnp.int32)
        return (jnp.zeros((0, k), jnp.int32),
                jnp.zeros((0, k), jnp.float32), z, z)
    outs = []
    start = 0
    for take, bucket in plan_chunks(total, buckets, multiple_of=dp):
        sl = slice(start, start + take)
        q = xq[sl]
        prog = program.take(sl)
        pi, pd = pre_ids[:, sl], pre_d[:, sl]
        up = use_pre[:, sl]
        if take < bucket:
            pad = bucket - take
            q = pad_rows(q, pad)
            prog = jax.tree_util.tree_map(lambda a: pad_rows(a, pad), prog)
            pi, pd = _pad_queries(pi, pad), _pad_queries(pd, pad)
            up = _pad_queries(up, pad)
        key = (bucket, k, ef, variant, m, m_beta, metric, compressed_level0,
               max_expansions, program.shape_sig, spec, "corpus")
        fn = cache.get(key, lambda: _build_corpus_variant(
            cache, key, statics, dp, cp))
        # host fetch on purpose — see the docstring's sharding caveat
        ids, d, dcs, hps = jax.device_get(
            fn(corpus, q, prog, aux, pi, pd, up, alive))
        outs.append((ids[:take], d[:take], dcs[:, :take], hps[:, :take]))
        start += take
    ids = jnp.asarray(np.concatenate([o[0] for o in outs]))
    d = jnp.asarray(np.concatenate([o[1] for o in outs]))
    dist_comps = jnp.asarray(np.concatenate([o[2] for o in outs], axis=1))
    hops = jnp.asarray(np.concatenate([o[3] for o in outs], axis=1))
    return ids, d, dist_comps, hops
