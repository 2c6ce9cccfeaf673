"""High-level hybrid-search index with ACORN's cost-based routing (§5.2).

``HybridIndex`` owns the vectors, attribute table, the ACORN graph, a
selectivity sketch, and implements the paper's routing rule: queries whose
estimated selectivity falls below s_min = 1/γ are answered by pre-filtered
brute force (exact); all others traverse the predicate subgraph.

Query-plan API: :meth:`HybridIndex.search` takes a
:class:`repro.core.plan.SearchRequest` (queries + predicate trees or a
pre-compiled :class:`PredicateProgram` + k/ef/route) plus an optional
:class:`ExecutionSpec`.  Predicates compile ONCE into a fused columnar
program: one on-device pass yields every query's pass-mask, and one more
pass over the selectivity-sketch sample yields every routing estimate —
replacing the legacy per-predicate host↔device round trips.  The old
``search(xq, predicates, ..., use_kernel=...)`` knob-kwarg call style is
retired: passing a legacy knob raises ``TypeError`` naming the
``ExecutionSpec`` field.  Results come back as one typed
:class:`repro.core.plan.SearchResult` (tuple unpacking still works via
``__iter__`` for this release).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field, replace
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .baselines import prefilter_search
from .batched import (DEFAULT_BUCKETS, VariantCache, pad_rows, plan_chunks,
                      search_batch)
from .build import build_acorn_1, build_acorn_gamma
from .graph import INVALID, LayeredGraph, memory_bytes
from .plan import (ExecutionSpec, PredicateProgram, SearchRequest,
                   SearchResult, compile_predicates, resolve_execution_spec)
from .predicates import (AttributeTable, Predicate, SelectivitySketch)

Array = jax.Array


@dataclass
class AcornConfig:
    M: int = 16
    gamma: int = 8
    m_beta: Optional[int] = None       # default 2M
    ef_search: int = 64
    variant: str = "acorn-gamma"       # or "acorn-1"
    metric: str = "l2"
    compress: bool = True
    max_expansions: int = 512
    # execution knobs (batched kernel-fused pipeline); bundled on demand
    # into an ExecutionSpec by .execution_spec()
    use_kernel: bool = False           # gather_distance Pallas kernel
    interpret: bool = False            # interpret=True runs the kernel on CPU
    # neighbor_expand Pallas kernel (fused 2-hop gather/filter/dedup/pack);
    # None follows use_kernel
    expand_kernel: Optional[bool] = None
    buckets: Tuple[int, ...] = DEFAULT_BUCKETS  # jit batch buckets
    # query-data-parallel devices for the graph route: 1 = single device,
    # None/0 = all local devices, N = min(N, local device count)
    data_parallel: Optional[int] = 1
    # corpus-mesh axis size for corpus-sharded serving
    # (repro.distributed.corpus_parallel via ServingEngine): None/0 = auto
    # (one device per corpus shard when the host has them); an explicit
    # value must equal the engine's shard count. A single HybridIndex is
    # always one corpus shard — its own searches run with the knob at 1.
    corpus_parallel: Optional[int] = None

    @property
    def s_min(self) -> float:
        return 1.0 / self.gamma

    def resolved_m_beta(self) -> int:
        return self.m_beta if self.m_beta is not None else 2 * self.M

    def execution_spec(self) -> ExecutionSpec:
        """This config's execution knobs as one frozen ExecutionSpec."""
        return ExecutionSpec(
            use_kernel=self.use_kernel, interpret=self.interpret,
            expand_kernel=self.expand_kernel,
            data_parallel=self.data_parallel,
            corpus_parallel=self.corpus_parallel)


@dataclass
class HybridIndex:
    x: Array
    table: AttributeTable
    graph: LayeredGraph
    config: AcornConfig
    sketch: SelectivitySketch
    build_seconds: float = 0.0
    # compiled-variant cache: one trace per (jit bucket, search config)
    cache: VariantCache = field(default_factory=VariantCache)

    # ------------------------------------------------------------------
    @staticmethod
    def build(x: Array, table: AttributeTable, config: AcornConfig,
              seed: int = 0) -> "HybridIndex":
        key = jax.random.PRNGKey(seed)
        t0 = time.perf_counter()
        if config.variant == "acorn-gamma":
            graph = build_acorn_gamma(
                x, key, M=config.M, gamma=config.gamma,
                m_beta=config.resolved_m_beta(), compress=config.compress)
        elif config.variant == "acorn-1":
            graph = build_acorn_1(x, key, M=config.M)
        else:
            raise ValueError(config.variant)
        jax.block_until_ready(graph.neighbors[0])
        tti = time.perf_counter() - t0
        sketch = SelectivitySketch.build(table, seed=seed)
        return HybridIndex(x=x, table=table, graph=graph, config=config,
                           sketch=sketch, build_seconds=tti)

    # ------------------------------------------------------------------
    @property
    def index_bytes(self) -> int:
        return memory_bytes(self.graph)

    @property
    def total_bytes(self) -> int:
        return self.index_bytes + self.x.size * self.x.dtype.itemsize

    # ------------------------------------------------------------------
    def prefilter(self, xq: Array, masks: Array, k: int
                  ) -> Tuple[np.ndarray, np.ndarray]:
        """Exact pre-filtered brute force through the jit buckets.

        The §5.2 low-selectivity route, shared by :meth:`search` and the
        serving engine's corpus-sharded SPMD path (which threads these
        exact results into its kernel as per-(shard, query) overrides).
        Returns numpy (B, k) ids / dists; ids are local row indices.
        """
        cfg = self.config
        b = xq.shape[0]
        out_ids = np.full((b, k), INVALID, np.int32)
        out_d = np.full((b, k), np.inf, np.float32)
        xq, masks = jnp.asarray(xq), jnp.asarray(masks)
        start = 0
        for take, bucket in plan_chunks(b, cfg.buckets):
            sl = slice(start, start + take)
            q, msk = xq[sl], masks[sl]
            if take < bucket:
                q = pad_rows(q, bucket - take)
                msk = pad_rows(msk, bucket - take)
            ids, d = prefilter_search(q, self.x, msk, k, metric=cfg.metric)
            out_ids[sl] = np.asarray(ids)[:take]
            out_d[sl] = np.asarray(d)[:take]
            start += take
        return out_ids, out_d

    # ------------------------------------------------------------------
    def compile(self, predicates: Sequence[Predicate]) -> PredicateProgram:
        """Compile predicate trees against this index's table schema."""
        return compile_predicates(predicates, self.table)

    # ------------------------------------------------------------------
    def search(
        self,
        request: Union[SearchRequest, Array],
        predicates: Union[Sequence[Predicate], PredicateProgram, None] = None,
        k: int = 10,
        ef: Optional[int] = None,
        force_route: Optional[str] = None,
        spec: Optional[ExecutionSpec] = None,
        use_kernel: Optional[bool] = None,
        interpret: Optional[bool] = None,
        expand_kernel: Optional[bool] = None,
        data_parallel: Optional[int] = None,
        corpus_parallel: Optional[int] = None,
    ) -> SearchResult:
        """Batched hybrid search with per-query cost-based routing.

        New call style::

            index.search(SearchRequest(xq=q, predicates=preds, k=10),
                         spec=ExecutionSpec(use_kernel=True))

        ``request.predicates`` may be predicate trees (compiled here, one
        fused mask + estimate pass each) or a pre-compiled
        :class:`PredicateProgram` (compile once, search everywhere — the
        serving engine shares one program across shards).  ``spec=None``
        defers to ``config.execution_spec()``; a given spec's ``None``
        fields resolve the usual way (``expand_kernel`` follows
        ``use_kernel``); ``corpus_parallel`` must resolve to 1 here: one
        HybridIndex is one corpus shard — multi-shard SPMD dispatch lives
        in ``repro.distributed.corpus_parallel`` / ``ServingEngine``.

        Bare positional queries still wrap into a request, but the five
        retired legacy knob kwargs now raise ``TypeError`` naming the
        matching ``ExecutionSpec`` field.

        Both routes dispatch through the jit-bucketed batch pipeline: the
        graph route via :func:`repro.core.batched.search_batch` (with this
        index's compiled-variant cache), the pre-filter route through the
        same bucket padding — so ragged request sizes never re-trace.

        Returns a :class:`repro.core.plan.SearchResult` (ids (B,k), dists
        (B,k), per-query stats + routes); legacy three-way unpacking
        ``ids, d, info = index.search(...)`` keeps working this release.
        """
        cfg = self.config
        if isinstance(request, SearchRequest):
            if predicates is not None:
                raise TypeError(
                    "pass predicates inside the SearchRequest, not alongside")
            xq = request.xq
            predicates = request.predicates
            k = request.k if request.k is not None else k
            ef = request.ef if request.ef is not None else ef
            force_route = (request.route if request.route is not None
                           else force_route)
        else:
            xq = request
        ef = ef or cfg.ef_search
        # base spec from config, except corpus_parallel: that AcornConfig
        # knob is engine-level geometry and deliberately NOT consulted here
        # — one HybridIndex is one corpus shard, so the field must resolve
        # to 1 (an explicit multi-shard request still fails loudly in
        # search_batch)
        base = replace(cfg.execution_spec(), corpus_parallel=None)
        spec = resolve_execution_spec(
            spec, "HybridIndex.search", base=base,
            use_kernel=use_kernel, interpret=interpret,
            expand_kernel=expand_kernel, data_parallel=data_parallel,
            corpus_parallel=corpus_parallel)

        b = xq.shape[0]
        if predicates is None:
            if force_route == "prefilter":
                raise ValueError(
                    "route='prefilter' (exact masked brute force) needs "
                    "predicates; pass TruePredicate() per query for an "
                    "explicit match-all")
            # unfiltered ANN: the plain-HNSW substrate (search_batch's
            # documented pass_masks=None fallback); no routing to price
            ids, d, stats = search_batch(
                self.graph, self.x, xq, None, k=k, ef=ef,
                variant=cfg.variant, m=cfg.M, m_beta=cfg.resolved_m_beta(),
                metric=cfg.metric, compressed_level0=False,
                max_expansions=cfg.max_expansions, spec=spec,
                buckets=cfg.buckets, cache=self.cache)
            return SearchResult(
                ids=ids, dists=d,
                stats=dict(selectivity_est=np.ones((b,)),
                           dist_comps=np.asarray(stats.dist_comps)),
                routes=np.full((b,), "graph"), legacy_arity=3)

        # -- compile once: one fused pass for masks, one for estimates --
        program = (predicates if isinstance(predicates, PredicateProgram)
                   else compile_predicates(predicates, self.table))
        if program.n_queries != b:
            raise ValueError(
                f"{b} queries but {program.n_queries} predicates")
        masks = program.evaluate(self.table)          # (B, n), one pass
        s_est = self.sketch.estimate_batch(program)   # (B,), one pass
        if force_route == "graph":
            use_pre = np.zeros(b, bool)
        elif force_route == "prefilter":
            use_pre = np.ones(b, bool)
        else:
            use_pre = s_est < cfg.s_min

        out_ids = np.full((b, k), INVALID, np.int32)
        out_d = np.full((b, k), np.inf, np.float32)
        dist_comps = np.zeros((b,), np.int64)

        pre_idx = np.nonzero(use_pre)[0]
        gr_idx = np.nonzero(~use_pre)[0]
        if len(pre_idx):
            ids_p, d_p = self.prefilter(xq[pre_idx], masks[pre_idx], k)
            out_ids[pre_idx] = ids_p
            out_d[pre_idx] = d_p
            dist_comps[pre_idx] = np.asarray(masks[pre_idx].sum(axis=1))
        if len(gr_idx):
            variant = cfg.variant
            ids, d, stats = search_batch(
                self.graph, self.x, xq[gr_idx], masks[gr_idx], k=k, ef=ef,
                variant=variant, m=cfg.M, m_beta=cfg.resolved_m_beta(),
                metric=cfg.metric,
                compressed_level0=cfg.compress and variant == "acorn-gamma",
                max_expansions=cfg.max_expansions, spec=spec,
                buckets=cfg.buckets, cache=self.cache)
            out_ids[gr_idx] = np.asarray(ids)
            out_d[gr_idx] = np.asarray(d)
            dist_comps[gr_idx] = np.asarray(stats.dist_comps)

        return SearchResult(
            ids=jnp.asarray(out_ids), dists=jnp.asarray(out_d),
            stats=dict(selectivity_est=np.asarray(s_est),
                       dist_comps=dist_comps),
            routes=np.where(use_pre, "prefilter", "graph"), legacy_arity=3)
