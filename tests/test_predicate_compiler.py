"""Predicate-compiler parity + query-plan API suite.

Three gates:

1. **Compiler parity** — ``compile_predicates`` + ``evaluate_program``
   must match the tree-walking interpreter (``evaluate``/
   ``evaluate_batch``) bit-identically over randomized expression trees:
   nested ``And``/``Or``/``Not``, empty ``OneOf``/``ContainsAny`` operand
   tuples, regex leaves, ``TruePredicate``, and row-sliced (``take``)
   tables — the bit-parity claim every downstream execution path
   (single-shard, query-parallel, corpus-SPMD) inherits.
2. **Regex leaf caching** — host-evaluated ``(column, pattern)`` bitmaps
   are computed once per table and sliced through ``take``; the compiled
   ``re`` object is shared process-wide.
3. **Legacy-kwarg removal** — the retired knob-kwarg call style fails
   loudly with a ``TypeError`` naming the ``ExecutionSpec`` replacement
   field (never a silent ignore); the ``ExecutionSpec`` style serves a
   golden-recall-shaped workload and the resolved spec is the single
   variant-cache key component.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AcornConfig, And, AttributeTable, Between,
                        ContainsAny, Equals, ExecutionSpec, HybridIndex, Not,
                        OneOf, Or, PredicateProgram, RegexMatch,
                        SearchRequest, SelectivitySketch, TruePredicate,
                        VariantCache, build_acorn_gamma, compile_predicates,
                        evaluate, evaluate_batch, evaluate_predicates,
                        hybrid_search, pack_multihot, search_batch)
from repro.data import make_lcps_dataset, make_workload

N_KW = 40


@pytest.fixture(scope="module")
def table():
    rng = np.random.default_rng(7)
    n = 600
    kw_lists = [list(rng.choice(N_KW, size=rng.integers(0, 5), replace=False))
                for _ in range(n)]
    words = ["alpha", "beta", "gamma", "delta", "epsilon"]
    caps = ["photo of " + " ".join(rng.choice(words,
                                              size=rng.integers(1, 4)))
            for _ in range(n)]
    return AttributeTable(
        int_cols={"label": jnp.asarray(rng.integers(0, 12, n)
                                       .astype(np.int32)),
                  "date": jnp.asarray(rng.integers(0, 100, n)
                                      .astype(np.int32))},
        bitset_cols={"kw": jnp.asarray(pack_multihot(kw_lists, N_KW))},
        str_cols={"cap": np.asarray(caps, dtype=object)},
        n_keywords={"kw": N_KW},
    )


def random_tree(rng, depth=0):
    """A random predicate expression tree over the fixture's schema."""
    leaves = [
        lambda: Equals("label", int(rng.integers(0, 12))),
        lambda: OneOf("label", tuple(
            int(v) for v in rng.choice(12, size=rng.integers(0, 5),
                                       replace=False))),
        lambda: Between("date", int(rng.integers(0, 60)),
                        int(rng.integers(40, 100))),
        lambda: ContainsAny("kw", tuple(
            int(v) for v in rng.choice(N_KW, size=rng.integers(0, 4),
                                       replace=False))),
        lambda: RegexMatch("cap", rf"\b{rng.choice(['alpha', 'beta', 'gamma'])}\b"),
        lambda: TruePredicate(),
    ]
    if depth >= 3 or rng.random() < 0.4:
        return leaves[int(rng.integers(0, len(leaves)))]()
    kind = rng.integers(0, 3)
    if kind == 2:
        return Not(random_tree(rng, depth + 1))
    parts = tuple(random_tree(rng, depth + 1)
                  for _ in range(int(rng.integers(1, 4))))
    return And(parts) if kind == 0 else Or(parts)


# ---------------------------------------------------------------------------
# 1. compiler parity
# ---------------------------------------------------------------------------


def test_compiled_matches_interpreter_randomized_trees(table):
    """Bit-identical masks over 3 seeds x 32 random heterogeneous trees."""
    for seed in range(3):
        rng = np.random.default_rng(100 + seed)
        preds = [random_tree(rng) for _ in range(32)]
        prog = compile_predicates(preds, table)
        got = np.asarray(prog.evaluate(table))
        want = np.asarray(evaluate_batch(preds, table))
        np.testing.assert_array_equal(got, want, err_msg=f"seed {seed}")


def test_compiled_edge_cases(table):
    preds = [
        OneOf("label", ()),                 # empty operand tuple -> all False
        ContainsAny("kw", ()),              # empty keyword set   -> all False
        TruePredicate(),
        Not(TruePredicate()),
        And((TruePredicate(),)),            # single-part connectives
        Or((Equals("label", 0),)),
        Not(Not(Equals("label", 3))),
        And((Or((Equals("label", 1), Equals("label", 2))),
             Not(Between("date", 0, 49)),
             ContainsAny("kw", (0, 1, 2)))),
    ]
    prog = compile_predicates(preds, table)
    got = np.asarray(prog.evaluate(table))
    want = np.asarray(evaluate_batch(preds, table))
    np.testing.assert_array_equal(got, want)
    assert not got[0].any() and not got[1].any()
    assert got[2].all() and not got[3].any()


def test_compiled_regex_leaves_and_dedup(table):
    """Regex leaves evaluate host-side once per (column, pattern) and are
    shared across the batch as aux rows."""
    p = RegexMatch("cap", r"\balpha\b")
    preds = [p, Not(p), p & Between("date", 0, 50), TruePredicate()]
    prog = compile_predicates(preds, table)
    assert prog.regex_leaves == (("cap", r"\balpha\b"),)  # deduped
    got = np.asarray(prog.evaluate(table))
    want = np.asarray(evaluate_batch(preds, table))
    np.testing.assert_array_equal(got, want)


def test_compiled_parity_on_take_sliced_table(table):
    """Programs are schema-compiled: the same program must evaluate
    bit-identically on row-sliced shards/samples of the table."""
    rng = np.random.default_rng(3)
    preds = [random_tree(rng) for _ in range(16)]
    prog = compile_predicates(preds, table)
    idx = rng.choice(table.n, size=137, replace=False)
    sub = table.take(idx)
    got = np.asarray(prog.evaluate(sub))
    want = np.asarray(evaluate_batch(preds, sub))
    np.testing.assert_array_equal(got, want)


def test_program_evaluates_by_name_across_column_orders(table):
    """Programs carry their compile-time schema and pack columns BY NAME:
    a table with the same columns in a different dict order evaluates
    bit-identically, and a table missing a column fails loudly."""
    reordered = AttributeTable(
        int_cols=dict(reversed(list(table.int_cols.items()))),
        bitset_cols=dict(table.bitset_cols),
        str_cols=dict(table.str_cols),
        n_keywords=dict(table.n_keywords))
    preds = [Equals("label", 3), Between("date", 10, 60),
             Equals("date", 7) & Equals("label", 1)]
    prog = compile_predicates(preds, table)
    np.testing.assert_array_equal(np.asarray(prog.evaluate(reordered)),
                                  np.asarray(evaluate_batch(preds, table)))
    missing = AttributeTable(int_cols={"label": table.int_cols["label"]},
                             bitset_cols={}, str_cols={}, n_keywords={})
    with pytest.raises(KeyError):
        prog.evaluate(missing)


def test_program_take_rows(table):
    rng = np.random.default_rng(4)
    preds = [random_tree(rng) for _ in range(10)]
    prog = compile_predicates(preds, table)
    sel = np.array([7, 2, 2, 9])
    got = np.asarray(prog.take(sel).evaluate(table))
    want = np.asarray(evaluate_batch([preds[i] for i in sel], table))
    np.testing.assert_array_equal(got, want)


def test_padded_rows_forced_false(table):
    """The corpus envelope pads attribute rows with zeros; n_valid must
    mask them out even when a predicate matches the zero value."""
    from repro.core import evaluate_program, pack_columns, regex_aux
    preds = [Equals("label", 0), Not(Equals("label", 999))]
    prog = compile_predicates(preds, table)
    cols = pack_columns(table)
    aux = regex_aux(table, prog.regex_leaves)
    pad = 50
    ints = jnp.pad(cols.ints, ((0, 0), (0, pad)))
    bitsets = jnp.pad(cols.bitsets, ((0, 0), (0, pad), (0, 0)))
    aux_p = jnp.pad(aux, ((0, 0), (0, pad)))
    got = np.asarray(evaluate_program(prog, ints, bitsets, aux_p,
                                      n_valid=jnp.asarray(table.n)))
    want = np.asarray(evaluate_batch(preds, table))
    np.testing.assert_array_equal(got[:, : table.n], want)
    assert not got[:, table.n:].any()  # Not(...) / Equals 0 hit zero pads


def test_evaluate_predicates_convenience(table):
    preds = [Equals("label", 1), Between("date", 5, 60)]
    np.testing.assert_array_equal(
        np.asarray(evaluate_predicates(preds, table)),
        np.asarray(evaluate_batch(preds, table)))


def test_sketch_estimate_batch_matches_legacy(table):
    """One fused pass == per-predicate estimates, exactly (bool sums below
    2^24 rows are order-independent in f32)."""
    sk = SelectivitySketch.build(table, sample_size=256, seed=0)
    rng = np.random.default_rng(5)
    preds = [random_tree(rng) for _ in range(24)]
    batched = sk.estimate_batch(preds)
    legacy = np.array(
        [float(jnp.mean(evaluate(p, sk.sample))) for p in preds])
    np.testing.assert_array_equal(batched, legacy)
    # pre-compiled program path agrees too
    prog = compile_predicates(preds, sk.sample)
    np.testing.assert_array_equal(sk.estimate_batch(prog), batched)


def test_compile_errors(table):
    with pytest.raises(ValueError):
        compile_predicates([], table)
    with pytest.raises(ValueError):
        compile_predicates([And(())], table)
    with pytest.raises(ValueError):
        compile_predicates([Equals("nope", 1)], table)


# ---------------------------------------------------------------------------
# 2. regex leaf-mask caching
# ---------------------------------------------------------------------------


def test_regex_mask_cached_per_column_pattern(table, monkeypatch):
    # a genuinely fresh table (take() would inherit the fixture's cache)
    t = AttributeTable(int_cols=dict(table.int_cols),
                       bitset_cols=dict(table.bitset_cols),
                       str_cols=dict(table.str_cols),
                       n_keywords=dict(table.n_keywords))
    calls = {"n": 0}
    import repro.core.predicates as pred_mod

    class CountingPattern:
        def __init__(self, rx):
            self._rx = rx

        def search(self, *a, **kw):
            calls["n"] += 1
            return self._rx.search(*a, **kw)

    import re as re_mod
    monkeypatch.setattr(pred_mod, "_compiled_regex",
                        lambda pat: CountingPattern(re_mod.compile(pat)))
    p = RegexMatch("cap", r"\bgamma\b$")  # pattern no other test uses
    m1 = np.asarray(evaluate(p, t))
    first = calls["n"]
    assert first == t.n  # one scan
    m2 = np.asarray(evaluate(p, t))          # interpreter hit
    m3 = np.asarray(compile_predicates([p], t).evaluate(t))[0]  # program hit
    assert calls["n"] == first               # no rescans
    np.testing.assert_array_equal(m1, m2)
    np.testing.assert_array_equal(m1, m3)


def test_regex_cache_slices_through_take(table):
    t = table.take(np.arange(table.n))  # fresh cache
    p = RegexMatch("cap", r"\bbeta\b")
    full = t.regex_mask("cap", p.pattern)
    idx = np.arange(0, t.n, 3)
    sub = t.take(idx)
    assert ("cap", p.pattern) in sub._plan_cache["regex"]  # inherited
    np.testing.assert_array_equal(sub._plan_cache["regex"][("cap", p.pattern)],
                                  full[idx])
    np.testing.assert_array_equal(np.asarray(evaluate(p, sub)), full[idx])


def test_compiled_re_object_shared():
    from repro.core.predicates import _RE_CACHE, _compiled_regex
    r1 = _compiled_regex(r"share-me-\d+")
    r2 = _compiled_regex(r"share-me-\d+")
    assert r1 is r2
    assert r"share-me-\d+" in _RE_CACHE


# ---------------------------------------------------------------------------
# 3. legacy-kwarg removal + ExecutionSpec keys
# ---------------------------------------------------------------------------

# golden-recall-cell geometry (tests/test_golden_recall.py), small variant
N, D, CARD, SEED = 800, 12, 8, 0
B, K, EF, M, M_BETA = 16, 10, 32, 8, 16


@pytest.fixture(scope="module")
def golden_cell():
    ds = make_lcps_dataset(n=N, d=D, card=CARD, seed=SEED)
    wl = make_workload(ds, kind="equals", n_queries=B, k=K, seed=1,
                       card=CARD)
    g = build_acorn_gamma(ds.x, jax.random.PRNGKey(SEED), M=M, gamma=CARD,
                          m_beta=M_BETA)
    return ds, wl, g


def test_hybrid_search_legacy_kwargs_raise(golden_cell):
    """The retired per-call knobs fail loudly with a migration hint that
    names the ExecutionSpec field — never a silent ignore."""
    ds, wl, g = golden_cell
    masks = wl.masks(ds)
    kw = dict(k=K, ef=EF, variant="acorn-gamma", m=M, m_beta=M_BETA)
    ids_new, d_new, _ = hybrid_search(g, ds.x, wl.xq, masks,
                                      spec=ExecutionSpec(), **kw)
    assert ids_new.shape == (B, K)
    with pytest.raises(
            TypeError,
            match=r"use_kernel.*were removed.*"
                  r"spec=ExecutionSpec\(use_kernel=\.\.\.\)"):
        hybrid_search(g, ds.x, wl.xq, masks, use_kernel=False,
                      interpret=True, **kw)


def test_search_batch_legacy_kwargs_raise_and_keys_on_spec(golden_cell):
    ds, wl, g = golden_cell
    masks = wl.masks(ds)
    kw = dict(k=K, ef=EF, variant="acorn-gamma", m=M, m_beta=M_BETA,
              buckets=(B,))
    cache = VariantCache()
    ids, d, _ = search_batch(g, ds.x, wl.xq, masks, cache=cache,
                             spec=ExecutionSpec(), **kw)
    assert ids.shape == (B, K)
    # the resolved ExecutionSpec is the single execution-knob key component
    (key,) = cache.fns
    spec = key[-1]
    assert isinstance(spec, ExecutionSpec)
    assert spec == ExecutionSpec(use_kernel=False, interpret=False,
                                 expand_kernel=False, data_parallel=1,
                                 corpus_parallel=1)
    # every retired kwarg is named in the error, sorted, with its hint
    with pytest.raises(
            TypeError,
            match=r"\['data_parallel', 'use_kernel'\] were removed.*"
                  r"spec=ExecutionSpec\(data_parallel=\.\.\.\), "
                  r"spec=ExecutionSpec\(use_kernel=\.\.\.\)"):
        search_batch(g, ds.x, wl.xq, masks, cache=VariantCache(),
                     use_kernel=False, data_parallel=1, **kw)


def test_search_batch_rejects_spec_plus_legacy_knobs(golden_cell):
    """A migrated spec= call that still carries a legacy knob fails the
    same way a pure-legacy call does."""
    ds, wl, g = golden_cell
    with pytest.raises(TypeError, match="were removed"):
        search_batch(g, ds.x, wl.xq, wl.masks(ds), k=K, ef=EF,
                     spec=ExecutionSpec(), use_kernel=True)


def test_hybrid_index_legacy_kwargs_raise_and_request_parity(golden_cell):
    ds, wl, _ = golden_cell
    cfg = AcornConfig(M=M, gamma=CARD, m_beta=M_BETA, ef_search=EF,
                      buckets=(B,))
    idx = HybridIndex.build(ds.x, ds.table, cfg, seed=SEED)
    req = SearchRequest(xq=wl.xq, predicates=wl.predicates, k=K)
    ids_new, d_new, info_new = idx.search(req)
    # positional (xq, predicates) style without knobs: same bits
    ids_old, d_old, info_old = idx.search(wl.xq, wl.predicates, k=K)
    np.testing.assert_array_equal(np.asarray(ids_new), np.asarray(ids_old))
    np.testing.assert_array_equal(np.asarray(d_new), np.asarray(d_old))
    np.testing.assert_array_equal(info_new["routes"], info_old["routes"])
    np.testing.assert_array_equal(info_new["selectivity_est"],
                                  info_old["selectivity_est"])
    # the retired kwargs fail loudly, naming the ExecutionSpec fields
    with pytest.raises(TypeError,
                       match=r"HybridIndex\.search.*were removed"):
        idx.search(wl.xq, wl.predicates, k=K, use_kernel=False,
                   interpret=True, data_parallel=1)
    # pre-compiled program through the request: same bits again
    prog = idx.compile(wl.predicates)
    assert isinstance(prog, PredicateProgram)
    ids_p, d_p, _ = idx.search(SearchRequest(xq=wl.xq, predicates=prog, k=K))
    np.testing.assert_array_equal(np.asarray(ids_new), np.asarray(ids_p))


def test_engine_spec_field_and_request_parity(golden_cell):
    ds, wl, _ = golden_cell
    from repro.serve import EngineConfig, ServingEngine
    acorn = AcornConfig(M=M, gamma=CARD, m_beta=M_BETA, ef_search=EF,
                        buckets=(B,))
    eng = ServingEngine(ds.x, ds.table, acorn,
                        EngineConfig(batch_size=B, k=K, ef=EF, n_shards=2,
                                     spec=ExecutionSpec()))
    i_pos, d_pos = eng.serve(wl.xq, wl.predicates)
    i_req, d_req = eng.serve(
        SearchRequest(xq=wl.xq, predicates=wl.predicates, k=K))
    np.testing.assert_array_equal(np.asarray(i_pos), np.asarray(i_req))
    np.testing.assert_array_equal(np.asarray(d_pos), np.asarray(d_req))


def test_search_request_k_defers_to_call_site(golden_cell):
    """SearchRequest.k=None must not shadow an explicit k kwarg."""
    ds, wl, _ = golden_cell
    cfg = AcornConfig(M=M, gamma=CARD, m_beta=M_BETA, ef_search=EF,
                      buckets=(B,))
    idx = HybridIndex.build(ds.x, ds.table, cfg, seed=SEED)
    ids, d, _ = idx.search(SearchRequest(xq=wl.xq,
                                         predicates=wl.predicates), k=7)
    assert ids.shape == (B, 7) and d.shape == (B, 7)
    ids2, _, _ = idx.search(SearchRequest(xq=wl.xq,
                                          predicates=wl.predicates, k=5))
    assert ids2.shape == (B, 5)


def test_search_request_none_predicates_runs_unfiltered(golden_cell):
    """predicates=None is the documented unfiltered-ANN path on
    HybridIndex; the serving engine rejects it with a clear error."""
    from repro.core import search_batch as sb
    from repro.serve import EngineConfig, ServingEngine
    ds, wl, _ = golden_cell
    cfg = AcornConfig(M=M, gamma=CARD, m_beta=M_BETA, ef_search=EF,
                      buckets=(B,))
    idx = HybridIndex.build(ds.x, ds.table, cfg, seed=SEED)
    ids, d, info = idx.search(SearchRequest(xq=wl.xq, k=K, ef=EF))
    want_ids, want_d, _ = sb(idx.graph, ds.x, wl.xq, None, k=K, ef=EF,
                             variant=cfg.variant, m=M, m_beta=M_BETA,
                             buckets=(B,), cache=VariantCache())
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(want_ids))
    np.testing.assert_array_equal(np.asarray(d), np.asarray(want_d))
    assert (info["routes"] == "graph").all()
    eng = ServingEngine(ds.x, ds.table, cfg,
                        EngineConfig(batch_size=B, k=K, n_shards=1))
    with pytest.raises(TypeError, match="requires predicates"):
        eng.serve(SearchRequest(xq=wl.xq, k=K))
    # an explicit exact route without predicates cannot be honored —
    # loud error, not silent approximate ANN
    with pytest.raises(ValueError, match="needs predicates"):
        idx.search(SearchRequest(xq=wl.xq, k=K, route="prefilter"))


def test_engine_rejects_foreign_schema_program(golden_cell, table):
    """The SPMD kernel reads corpus columns by compile-time slot number;
    a program compiled against another table's layout must be rejected,
    not silently evaluated against the wrong slots."""
    from repro.serve import EngineConfig, ServingEngine
    ds, wl, _ = golden_cell
    acorn = AcornConfig(M=M, gamma=CARD, m_beta=M_BETA, ef_search=EF,
                        buckets=(B,))
    eng = ServingEngine(ds.x, ds.table, acorn,
                        EngineConfig(batch_size=B, k=K, n_shards=1))
    foreign = compile_predicates(
        [Equals("label", 0)] * B, table)  # the HCPS-style fixture schema
    with pytest.raises(ValueError, match="compiled against schema"):
        eng.search_batch(SearchRequest(xq=wl.xq, predicates=foreign, k=K))


def test_stack_corpus_rejects_mismatched_shard_schemas(table):
    from repro.distributed import stack_corpus
    from repro.serve import EngineConfig, ServingEngine
    ds = make_lcps_dataset(n=300, d=8, card=4, seed=0)
    acorn = AcornConfig(M=8, gamma=4, m_beta=16, ef_search=16)
    eng = ServingEngine(ds.x, ds.table, acorn,
                        EngineConfig(batch_size=8, k=5, n_shards=2))
    with pytest.raises(ValueError, match="share one column layout"):
        stack_corpus([s.index.graph for s in eng.shards],
                     [s.index.x for s in eng.shards],
                     [s.base for s in eng.shards],
                     tables=[eng.shards[0].index.table, table])


def test_engine_honors_search_request_route(golden_cell):
    """SearchRequest.route must force the §5.2 router on the serving
    engine (it is documented and honored by HybridIndex.search); the
    forced prefilter route is exact brute force, so merged engine results
    must equal the global masked ground truth."""
    from repro.core import ground_truth
    from repro.serve import EngineConfig, ServingEngine
    ds, wl, _ = golden_cell
    acorn = AcornConfig(M=M, gamma=CARD, m_beta=M_BETA, ef_search=EF,
                        buckets=(B,))
    eng = ServingEngine(ds.x, ds.table, acorn,
                        EngineConfig(batch_size=B, k=K, ef=EF, n_shards=2))
    before = eng.stats["prefilter_routed"]
    ids, d = eng.serve(SearchRequest(xq=wl.xq, predicates=wl.predicates,
                                     k=K, route="prefilter"))
    # every (shard, query) took the exact route
    assert eng.stats["prefilter_routed"] - before == 2 * B
    gt = ground_truth(wl.xq, ds.x, wl.masks(ds), K)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(gt))
    before_g = eng.stats["graph_routed"]
    eng.serve(SearchRequest(xq=wl.xq, predicates=wl.predicates, k=K,
                            route="graph"))
    assert eng.stats["graph_routed"] - before_g == 2 * B


def test_engine_config_legacy_fields_raise():
    """EngineConfig's retired knob fields fail loudly AT CONSTRUCTION,
    naming the ExecutionSpec replacement — an old config can never be
    silently ignored or half-applied."""
    from repro.serve import EngineConfig
    with pytest.raises(
            TypeError,
            match=r"\['corpus_parallel', 'use_kernel'\] were removed.*"
                  r"spec=ExecutionSpec\(corpus_parallel=\.\.\.\)"):
        EngineConfig(batch_size=B, k=K, n_shards=1, use_kernel=False,
                     corpus_parallel=1)
    # spec alongside a legacy field is rejected too — the legacy field can
    # never silently win over a migrated config
    with pytest.raises(TypeError, match="were removed"):
        EngineConfig(batch_size=B, k=K, n_shards=1,
                     spec=ExecutionSpec(use_kernel=True), use_kernel=False)


def test_regex_caches_are_bounded(table):
    """Query-content-keyed caches evict FIFO — an unbounded stream of
    distinct patterns must not grow memory without limit."""
    from repro.core.predicates import REGEX_MASK_CACHE_MAX
    t = AttributeTable(int_cols=dict(table.int_cols),
                       bitset_cols=dict(table.bitset_cols),
                       str_cols=dict(table.str_cols),
                       n_keywords=dict(table.n_keywords))
    for i in range(REGEX_MASK_CACHE_MAX + 10):
        t.regex_mask("cap", rf"pattern-{i}")
    assert len(t._plan_cache["regex"]) == REGEX_MASK_CACHE_MAX
    # the earliest patterns were evicted, the newest survive
    assert ("cap", "pattern-0") not in t._plan_cache["regex"]
    assert ("cap", rf"pattern-{REGEX_MASK_CACHE_MAX + 9}") in \
        t._plan_cache["regex"]


def test_execution_spec_resolution_semantics():
    s = ExecutionSpec(use_kernel=True)
    assert s.expand_kernel is None and s.resolved_expand_kernel() is True
    r = s.resolve(data_parallel=4, corpus_parallel=2)
    assert r == ExecutionSpec(use_kernel=True, interpret=False,
                              expand_kernel=True, data_parallel=4,
                              corpus_parallel=2)
    assert hash(r) == hash(r)  # usable as a dict key
    assert s.overlay(interpret=None, use_kernel=False).use_kernel is False
