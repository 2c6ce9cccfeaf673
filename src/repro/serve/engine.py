"""Hybrid-search serving engine.

Operational wrapper around HybridIndex for production serving:

  * request batching — queries accumulate into ``batch_size`` chunks and
    each shard dispatches them through the jit-bucketed batch pipeline
    (``repro.core.batched.search_batch`` via ``HybridIndex.search``), so a
    ragged request stream runs against a handful of compiled shapes and the
    engine never re-traces per request shape;
  * compiled predicate programs — each batch's predicate trees compile
    ONCE (``repro.core.plan.compile_predicates``) into a columnar program
    shared by every shard: routing estimates come from one fused pass per
    shard sketch, and the SPMD path ships the program (operands, not
    masks) into the mesh kernel, which evaluates pass-masks in-program
    against shard-resident attribute columns — the host never
    materializes a ``(B, n_shard)`` mask per shard;
  * corpus sharding, two execution paths —

      - **SPMD (default when the mesh fits):** the per-shard indexes are
        stacked into a :class:`repro.distributed.corpus_parallel.ShardedCorpus`
        (graphs + vectors + packed attribute columns) and every batch runs
        as ONE program on a 2-D ``(data, corpus)`` mesh: corpus arrays
        split one shard per corpus device, queries + program rows split
        along ``data``, per-shard in-program predicate evaluation + search
        + local→global id offset + all-gather (distance, global-id)
        lexsort merge all inside the kernel
        (``repro.distributed.collectives.gathered_topk_merge``);
      - **host loop (:meth:`search_batch_host`):** the original Python
        walk over shards with a host-side merge — retained as the parity
        oracle for the SPMD path, for single-shard engines, and, in auto
        mode only, for hosts with fewer devices than corpus shards.  An
        explicit ``corpus_parallel`` that does not fit raises at
        construction.  ``stats["spmd_batches"]`` /
        ``stats["host_loop_batches"]`` count which path served.

    Both paths are bit-identical (gated in tests/test_corpus_parallel.py);
  * execution policy as ONE value — ``EngineConfig.spec``
    (:class:`repro.core.plan.ExecutionSpec`) bundles the kernel-routing
    knobs and the ``(data, corpus)`` mesh shape; the retired per-knob
    ``EngineConfig`` overlay fields raise ``TypeError`` with a migration
    hint (``None`` = unset defers to the AcornConfig spec);
  * typed results — every serving surface returns a
    :class:`repro.core.plan.SearchResult` (ids/dists/per-query stats +
    route summary + shed/degraded flags); ``ids, d = engine.serve(...)``
    tuple unpacking keeps working this release;
  * per-query cost-based routing (ACORN graph vs pre-filter, §5.2) — done
    inside HybridIndex on the host path; the SPMD path computes the same
    per-(shard, query) decisions from each shard's sketch (one fused
    estimate pass per shard) and threads them into the kernel as a route
    mask + exact pre-filter overrides;
  * straggler mitigation — in the multi-host layout each corpus shard is a
    stateless replica of an on-disk artifact; the engine simulates duplicate
    dispatch: every shard query optionally runs on a mirror, the merge takes
    whichever result set arrives first (deterministic merge here since both
    compute the same answer — the point is that the *protocol* tolerates a
    slow/failed shard);
  * failure recovery — ``rebuild_shard`` re-materializes a shard's subgraph
    from the checkpointed vectors and verifies search results are unchanged.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax.numpy as jnp
import numpy as np

from repro.core import AcornConfig, HybridIndex, Predicate, VariantCache
from repro.core.plan import (ExecutionSpec, PredicateProgram, SearchRequest,
                             SearchResult, TableSchema, _KNOB_NAMES,
                             compile_predicates, sentinel_result)
from repro.core.predicates import AttributeTable
from repro.distributed.collectives import merge_topk  # noqa: F401  (re-export)
from repro.distributed.corpus_parallel import (ShardedCorpus,
                                               corpus_search_batch,
                                               place_corpus,
                                               resolve_corpus_mesh_shape,
                                               stack_corpus, stack_regex_aux)

Predicates = Union[Sequence[Predicate], PredicateProgram]


@dataclasses.dataclass
class EngineConfig:
    batch_size: int = 64
    k: int = 10
    ef: int = 64
    n_shards: int = 1
    duplicate_dispatch: bool = False  # straggler mitigation (mirrored shards)
    # execution policy as one value; None = derive from AcornConfig
    spec: Optional[ExecutionSpec] = None
    # RETIRED legacy per-knob overlay: the fields remain declared so that
    # old configs fail with a migration hint instead of a silent ignore —
    # any non-None value raises TypeError in __post_init__
    use_kernel: Optional[bool] = None
    interpret: Optional[bool] = None
    expand_kernel: Optional[bool] = None
    data_parallel: Optional[int] = None
    corpus_parallel: Optional[int] = None
    host_fallback: bool = False  # force the host-loop oracle path

    def __post_init__(self):
        passed = sorted(n for n in _KNOB_NAMES
                        if getattr(self, n) is not None)
        if passed:
            hints = ", ".join(f"spec=ExecutionSpec({n}=...)" for n in passed)
            raise TypeError(
                f"EngineConfig: the legacy knob fields {passed} were "
                f"removed; pass {hints} instead")


@dataclasses.dataclass
class _Shard:
    index: HybridIndex
    base: int                  # global id offset
    healthy: bool = True


class ServingEngine:
    """Shards a corpus row-wise, builds one ACORN index per shard, serves
    batched hybrid queries with global top-k merge — SPMD on a
    ``(data, corpus)`` mesh when it fits, host loop otherwise."""

    def __init__(self, x, table: AttributeTable, acorn: AcornConfig,
                 cfg: EngineConfig, seed: int = 0):
        self.cfg = cfg
        self.acorn = acorn
        self.spmd_mesh_shape()  # an explicit mesh that does not fit raises
        n = x.shape[0]
        per = (n + cfg.n_shards - 1) // cfg.n_shards
        self.shards: List[_Shard] = []
        self._x = x
        self._table = table
        for s in range(cfg.n_shards):
            lo, hi = s * per, min((s + 1) * per, n)
            idx = np.arange(lo, hi)
            sub = HybridIndex.build(x[lo:hi], table.take(idx), acorn,
                                    seed=seed + s)
            self.shards.append(_Shard(index=sub, base=lo))
        self.stats: Dict[str, float] = {"queries": 0, "batches": 0,
                                        "prefilter_routed": 0,
                                        "graph_routed": 0,
                                        "duplicated_dispatches": 0,
                                        "spmd_batches": 0,
                                        "host_loop_batches": 0}
        # SPMD state: stacked corpus (rebuilt lazily after rebuild_shard),
        # per-regex-leaf-set aux bitmaps, and the compiled-variant cache
        # for the mesh kernels
        self._corpus: Optional[ShardedCorpus] = None
        self._corpus_mesh: Optional[Tuple[int, int]] = None
        self._aux_cache: Dict[tuple, "jnp.ndarray"] = {}
        self.spmd_cache = VariantCache()

    # ------------------------------------------------------------------
    # execution-spec + SPMD geometry resolution
    # ------------------------------------------------------------------
    def execution_spec(self) -> ExecutionSpec:
        """The engine's resolved execution policy: ``EngineConfig.spec``
        when set, else the AcornConfig spec.  (The legacy per-knob
        EngineConfig overlay is retired — ``__post_init__`` rejects it.)"""
        if self.cfg.spec is not None:
            return self.cfg.spec
        return self.acorn.execution_spec()

    def spmd_mesh_shape(self) -> Optional[Tuple[int, int]]:
        """The ``(data, corpus)`` mesh the SPMD path would run on, or
        ``None`` when this engine serves through the host loop.  Raises
        when ``corpus_parallel`` is set explicitly and the host cannot
        fit the mesh."""
        if self.cfg.host_fallback:
            return None
        spec = self.execution_spec()
        return resolve_corpus_mesh_shape(
            self.cfg.n_shards, data_parallel=spec.data_parallel,
            corpus_parallel=spec.corpus_parallel)

    def sharded_corpus(self) -> ShardedCorpus:
        """The stacked corpus as the SPMD path holds it: one shard per
        corpus device of :meth:`spmd_mesh_shape`, which must resolve
        (restacked after ``rebuild_shard``)."""
        shape = self.spmd_mesh_shape()
        if shape is None:
            raise ValueError("this engine serves through the host loop; "
                             "it holds no sharded corpus")
        if self._corpus is None or self._corpus_mesh != shape:
            self._corpus = place_corpus(stack_corpus(
                [s.index.graph for s in self.shards],
                [s.index.x for s in self.shards],
                [s.base for s in self.shards],
                tables=[s.index.table for s in self.shards]), *shape)
            self._corpus_mesh = shape
        return self._corpus

    def compile(self, predicates: Sequence[Predicate]) -> PredicateProgram:
        """Compile predicate trees once against the corpus schema; the
        program is valid for every shard (``take`` preserves the schema)
        and for both execution paths."""
        return compile_predicates(predicates, self._table)

    @staticmethod
    def _unpack(request, predicates):
        if isinstance(request, SearchRequest):
            if predicates is not None:
                raise TypeError(
                    "pass predicates inside the SearchRequest, not alongside")
            return (request.xq, request.predicates, request.k, request.ef,
                    request.route)
        return request, predicates, None, None, None

    # ------------------------------------------------------------------
    def search_batch(self, request: Union[SearchRequest, "jnp.ndarray"],
                     predicates: Optional[Predicates] = None):
        """One batched step across all shards + merge (SPMD when the mesh
        resolves, host loop otherwise — bit-identical either way; the
        ``spmd_batches`` / ``host_loop_batches`` stats count which).

        Accepts a :class:`SearchRequest` (whose ``k``/``ef``/``route``
        override the engine defaults for this call) or the legacy
        ``(xq, predicates)`` pair; ``predicates`` may be trees or a
        pre-compiled program.  Returns a :class:`SearchResult`
        (``ids, d = ...`` unpacking still works).
        """
        xq, preds, k, ef, route = self._unpack(request, predicates)
        shape = self.spmd_mesh_shape()
        if shape is None:
            return self._search_batch_host(xq, preds, k=k, ef=ef,
                                           route=route)
        return self._search_batch_spmd(xq, preds, *shape, k=k, ef=ef,
                                       route=route)

    # ------------------------------------------------------------------
    def _program(self, preds: Predicates, b: int) -> PredicateProgram:
        if preds is None:
            raise TypeError(
                "ServingEngine requires predicates (trees or a compiled "
                "program); pass TruePredicate() per query for match-all")
        if isinstance(preds, PredicateProgram):
            # the SPMD kernel reads corpus columns by compile-time slot
            # number (no name lookup on device) — a program compiled
            # against a different column layout would silently read the
            # wrong slots, so reject it here at the public surface
            schema = TableSchema.of(self._table)
            if preds.schema is not None and preds.schema != schema:
                raise ValueError(
                    f"program compiled against schema {preds.schema} but "
                    f"this engine's corpus has {schema} — compile with "
                    "engine.compile(...) (shards share that one layout)")
            prog = preds
        else:
            prog = self.compile(preds)
        if prog.n_queries != b:
            raise ValueError(f"{b} queries but {prog.n_queries} predicates")
        return prog

    def _regex_aux(self, program: PredicateProgram,
                   n_max: int) -> "jnp.ndarray":
        """Stacked per-shard regex-leaf bitmaps, cached per leaf set —
        steady-state streams reuse one device-resident block instead of
        re-stacking and re-transferring (S, A, n_max) every batch."""
        aux = self._aux_cache.get(program.regex_leaves)
        if aux is None:
            aux = stack_regex_aux([s.index.table for s in self.shards],
                                  n_max, program.regex_leaves)
            if len(self._aux_cache) >= 64:  # unbounded predicate streams
                self._aux_cache.pop(next(iter(self._aux_cache)))
            self._aux_cache[program.regex_leaves] = aux
        return aux

    def _search_batch_spmd(self, xq, preds: Predicates, dp: int, cp: int,
                           k: Optional[int] = None, ef: Optional[int] = None,
                           route: Optional[str] = None):
        """The mesh-native path: the compiled program + routing/fault
        state thread into one SPMD kernel per jit bucket; predicate
        masks are evaluated in-program on each corpus device."""
        cfg, acorn = self.cfg, self.acorn
        b = xq.shape[0]
        k = cfg.k if k is None else k
        ef = (ef or cfg.ef) or acorn.ef_search
        n_shards = cfg.n_shards
        corpus = self.sharded_corpus()
        n_max = corpus.x.shape[1]

        program = self._program(preds, b)
        # host-only (regex) leaves: per-shard cached bitmaps, not masks
        aux = self._regex_aux(program, n_max)

        use_pre = np.zeros((n_shards, b), bool)
        pre_ids = np.full((n_shards, b, k), -1, np.int32)
        pre_d = np.full((n_shards, b, k), np.inf, np.float32)
        alive = np.zeros((n_shards,), bool)
        mirrors = 2 if (cfg.duplicate_dispatch and n_shards > 1) else 1
        for s, shard in enumerate(self.shards):
            if not shard.healthy:
                if mirrors > 1:
                    # the mirror replica answers for the failed primary —
                    # identical result, one duplicated dispatch on the wire
                    self.stats["duplicated_dispatches"] += 1
                else:
                    continue  # shard contributes nothing this batch
            alive[s] = True
            # §5.2 cost-based routing, per (shard, query): each shard's own
            # selectivity sketch decides, exactly like HybridIndex.search —
            # one fused estimate pass per shard instead of B round trips;
            # a request route overrides the router, as on the host path
            if route == "graph":
                pre = np.zeros(b, bool)
            elif route == "prefilter":
                pre = np.ones(b, bool)
            else:
                s_est = shard.index.sketch.estimate_batch(program)
                pre = s_est < acorn.s_min
            use_pre[s] = pre
            if pre.any():
                qidx = np.nonzero(pre)[0]
                # the exact route needs real masks, but only for its own
                # (shard, query) pairs — evaluated on device from the
                # program rows, never a full (B, n_shard) host block
                sub_masks = program.take(qidx).evaluate(shard.index.table)
                ids_p, d_p = shard.index.prefilter(xq[qidx], sub_masks, k)
                pre_ids[s, qidx] = ids_p
                pre_d[s, qidx] = d_p
            self.stats["prefilter_routed"] += int(pre.sum())
            self.stats["graph_routed"] += int(b - pre.sum())

        self.stats["queries"] += b
        self.stats["batches"] += 1
        self.stats["spmd_batches"] += 1
        if not alive.any():
            # every shard (and mirror) down: degrade to an empty result set
            return sentinel_result(b, k)

        variant = acorn.variant
        spec = self.execution_spec().resolve(data_parallel=dp,
                                             corpus_parallel=cp)
        ids, d, dcs, _ = corpus_search_batch(
            corpus, xq, program, aux, jnp.asarray(pre_ids),
            jnp.asarray(pre_d), jnp.asarray(use_pre), jnp.asarray(alive),
            k=k, ef=ef, variant=variant, m=acorn.M,
            m_beta=acorn.resolved_m_beta(), metric=acorn.metric,
            compressed_level0=acorn.compress and variant == "acorn-gamma",
            max_expansions=acorn.max_expansions, spec=spec,
            buckets=acorn.buckets, cache=self.spmd_cache)
        return self._result(ids, d,
                            dist_comps=np.asarray(dcs)[alive].sum(axis=0),
                            pre_counts=use_pre[alive].sum(axis=0),
                            n_alive=int(alive.sum()),
                            degraded=not alive.all())

    # ------------------------------------------------------------------
    @staticmethod
    def _result(ids, d, dist_comps, pre_counts, n_alive: int,
                degraded: bool) -> SearchResult:
        """Assemble the engine's typed result: per-query route summary
        across the shards that answered (``mixed`` = the shard sketches
        disagreed), total distance comps, and the degraded flag (some
        configured shard contributed nothing — results are incomplete
        but serving continued)."""
        b = int(ids.shape[0])
        pre_counts = np.asarray(pre_counts)
        routes = np.where(pre_counts >= n_alive, "prefilter",
                          np.where(pre_counts == 0, "graph", "mixed"))
        return SearchResult(
            ids=ids, dists=d,
            stats=dict(dist_comps=np.asarray(dist_comps)),
            routes=routes, shed=np.zeros((b,), bool),
            degraded=np.full((b,), degraded), legacy_arity=2)

    # ------------------------------------------------------------------
    def search_batch_host(self, request: Union[SearchRequest, "jnp.ndarray"],
                          predicates: Optional[Predicates] = None):
        """The host-side shard walk + merge — the parity oracle for the
        SPMD path and the fallback when the mesh doesn't fit."""
        xq, preds, k, ef, route = self._unpack(request, predicates)
        return self._search_batch_host(xq, preds, k=k, ef=ef, route=route)

    def _search_batch_host(self, xq, preds: Predicates,
                           k: Optional[int] = None,
                           ef: Optional[int] = None,
                           route: Optional[str] = None):
        cfg = self.cfg
        b = xq.shape[0]
        k = cfg.k if k is None else k
        ef = ef if ef is not None else cfg.ef
        # compile once, share across shards (one schema corpus-wide); the
        # per-shard spec pins corpus_parallel: each HybridIndex is exactly
        # one corpus shard, whatever mesh geometry the engine runs
        program = self._program(preds, b)
        shard_spec = dataclasses.replace(self.execution_spec(),
                                         corpus_parallel=None)
        all_ids, all_d = [], []
        pre_counts = np.zeros((b,), np.int64)
        dist_comps = np.zeros((b,), np.int64)
        n_alive = 0
        for shard in self.shards:
            mirrors = 2 if (cfg.duplicate_dispatch and cfg.n_shards > 1) else 1
            result = None
            for attempt in range(mirrors):
                if not shard.healthy and attempt == 0:
                    if mirrors > 1:
                        # only count an actual mirror dispatch; without
                        # duplicate_dispatch the unhealthy primary simply
                        # drops out and no duplicate work happens
                        self.stats["duplicated_dispatches"] += 1
                    continue  # primary "failed"; mirror answers
                result = shard.index.search(
                    SearchRequest(xq=xq, predicates=program, k=k, ef=ef,
                                  route=route),
                    spec=shard_spec)
                break
            if result is None:  # all mirrors down -> shard contributes none
                continue
            n_alive += 1
            gids = jnp.where(result.ids >= 0, result.ids + shard.base, -1)
            all_ids.append(gids)
            all_d.append(result.dists)
            pre_counts += result.routes == "prefilter"
            dist_comps += np.asarray(result.stats["dist_comps"])
            self.stats["prefilter_routed"] += int(
                (result.routes == "prefilter").sum())
            self.stats["graph_routed"] += int(
                (result.routes == "graph").sum())
        self.stats["queries"] += b
        self.stats["batches"] += 1
        self.stats["host_loop_batches"] += 1
        if not all_ids:
            # every shard (and mirror) down: degrade to an empty result set
            # instead of crashing the serving path — availability first
            return sentinel_result(b, k)
        ids = jnp.concatenate(all_ids, axis=1)
        d = jnp.concatenate(all_d, axis=1)
        mi, md = merge_topk(ids, d, k)
        return self._result(mi, md, dist_comps=dist_comps,
                            pre_counts=pre_counts, n_alive=n_alive,
                            degraded=n_alive < cfg.n_shards)

    # ------------------------------------------------------------------
    def serve(self, request: Union[SearchRequest, "jnp.ndarray"],
              predicates: Optional[Predicates] = None):
        """Batch an arbitrary request stream into cfg.batch_size chunks.

        Accepts a :class:`SearchRequest` or the legacy ``(xq,
        predicates)`` pair; predicate trees compile once for the whole
        stream and the compiled program is row-sliced per chunk.  Chunks
        are NOT padded here: each path pads to its jit buckets
        (``HybridIndex.search`` per shard on the host loop,
        ``corpus_search_batch`` on the mesh), so ragged tails reuse the
        per-bucket compiled variants instead of minting a new shape."""
        xq, preds, k, ef, route = self._unpack(request, predicates)
        b = self.cfg.batch_size
        n = xq.shape[0]
        program = self._program(preds, n)
        outs: List[SearchResult] = []
        for start in range(0, n, b):
            stop = min(start + b, n)
            req = SearchRequest(xq=xq[start:stop],
                                predicates=program.take(slice(start, stop)),
                                k=self.cfg.k if k is None else k, ef=ef,
                                route=route)
            outs.append(self.search_batch(req))
        return SearchResult.concatenate(outs)

    # ------------------------------------------------------------------
    def trace_counts(self) -> Dict[int, Dict[int, int]]:
        """Per-shard compiled-variant traces by jit bucket (regression
        guard: steady-state serving must not mint new shapes)."""
        return {s: shard.index.cache.bucket_traces()
                for s, shard in enumerate(self.shards)}

    def spmd_traces(self) -> Dict[int, int]:
        """SPMD-kernel traces by jit bucket (same steady-state guard for
        the mesh path)."""
        return self.spmd_cache.bucket_traces()

    # ------------------------------------------------------------------
    # fault tolerance
    # ------------------------------------------------------------------
    def fail_shard(self, s: int):
        self.shards[s].healthy = False

    def rebuild_shard(self, s: int, seed: int = 0):
        """Re-materialize a failed shard from the source-of-truth arrays
        (in production: from the checkpoint artifact)."""
        shard = self.shards[s]
        per = shard.index.x.shape[0]
        lo = shard.base
        idx = np.arange(lo, lo + per)
        shard.index = HybridIndex.build(self._x[lo:lo + per],
                                        self._table.take(idx), self.acorn,
                                        seed=seed + s)
        shard.healthy = True
        # restack the SPMD corpus + aux bitmaps on next dispatch
        self._corpus = None
        self._aux_cache.clear()
