"""Corpus-sharded SPMD serving: mesh resolution, shape-padding parity,
fault injection, and bit-identical agreement with the host-loop oracle.

The in-process half is device-count-agnostic (padding parity needs no
mesh; resolution logic adapts to whatever the host has).  The mesh half
needs 8 devices and — like test_distributed.py / test_query_parallel.py —
runs in a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8,
sweeping every (data, corpus) shape of an 8-device mesh: 2x4, 4x2, 1x8,
8x1.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AcornConfig, ExecutionSpec, hybrid_search
from repro.core.predicates import evaluate_batch
from repro.data import make_lcps_dataset, make_workload
from repro.distributed import (resolve_corpus_mesh_shape, shard_slice,
                               stack_corpus)
from repro.serve import EngineConfig, ServingEngine


# ---------------------------------------------------------------------------
# in-process: mesh-shape resolution + stacking/padding parity
# ---------------------------------------------------------------------------


def test_resolve_corpus_mesh_shape():
    ndev = jax.local_device_count()
    # auto: single shard stays on the plain path
    assert resolve_corpus_mesh_shape(1) is None
    # explicit single-shard request: SPMD with all devices on 'data'
    assert resolve_corpus_mesh_shape(1, corpus_parallel=1) == (ndev, 1)
    # more shards than devices: host fallback in auto mode only
    assert resolve_corpus_mesh_shape(ndev + 1) is None
    with pytest.raises(ValueError, match="only .* devices are local"):
        resolve_corpus_mesh_shape(ndev + 1, corpus_parallel=ndev + 1)
    # the corpus axis holds one shard per device — mismatches are errors
    with pytest.raises(ValueError):
        resolve_corpus_mesh_shape(2, corpus_parallel=3)
    if ndev >= 2:
        assert resolve_corpus_mesh_shape(2) == (ndev // 2, 2)
        assert resolve_corpus_mesh_shape(2, data_parallel=1) == (1, 2)
        # data axis clamps to the leftover budget
        assert resolve_corpus_mesh_shape(2, data_parallel=10 ** 6) == (
            ndev // 2, 2)


def test_engine_falls_back_without_devices():
    """n_shards beyond the host's devices serves through the host loop."""
    ndev = jax.local_device_count()
    ds = make_lcps_dataset(n=400, d=8, card=4, seed=0)
    wl = make_workload(ds, kind="equals", n_queries=5, k=5, seed=1, card=4)
    acorn = AcornConfig(M=8, gamma=4, m_beta=16, ef_search=16, buckets=(8,))
    eng = ServingEngine(ds.x, ds.table, acorn,
                        EngineConfig(batch_size=8, k=5, n_shards=ndev + 1))
    assert eng.spmd_mesh_shape() is None
    ids, d = eng.serve(wl.xq, wl.predicates)
    assert ids.shape == (5, 5)
    assert eng.spmd_traces() == {}  # nothing ran through the mesh
    # ... and the stats say which path served
    assert eng.stats["host_loop_batches"] == eng.stats["batches"] == 1
    assert eng.stats["spmd_batches"] == 0


def test_engine_raises_when_explicit_mesh_does_not_fit():
    """Asking for SPMD explicitly never quietly degrades to the host loop:
    the engine refuses before it builds any shard."""
    ndev = jax.local_device_count()
    ds = make_lcps_dataset(n=400, d=8, card=4, seed=0)
    acorn = AcornConfig(M=8, gamma=4, m_beta=16, ef_search=16, buckets=(8,))
    spec = ExecutionSpec(corpus_parallel=ndev + 1)
    with pytest.raises(ValueError, match="only .* devices are local"):
        ServingEngine(ds.x, ds.table, acorn,
                      EngineConfig(batch_size=8, k=5, n_shards=ndev + 1,
                                   spec=spec))


def test_stack_corpus_padding_is_search_invisible():
    """A shard's slice of the stacked (padded) corpus must search
    bit-identically to its own unpadded graph — the invariant the whole
    SPMD parity claim rests on."""
    ds = make_lcps_dataset(n=700, d=10, card=4, seed=0)
    wl = make_workload(ds, kind="equals", n_queries=7, k=5, seed=1, card=4)
    acorn = AcornConfig(M=8, gamma=4, m_beta=16, ef_search=24)
    # deliberately unequal shard sizes -> real padding on the small shard
    eng = ServingEngine(ds.x, ds.table, acorn,
                        EngineConfig(batch_size=8, k=5, n_shards=3))
    corpus = stack_corpus([s.index.graph for s in eng.shards],
                          [s.index.x for s in eng.shards],
                          [s.base for s in eng.shards])
    assert corpus.n_shards == 3
    n_max = max(int(s.index.x.shape[0]) for s in eng.shards)
    assert corpus.x.shape == (3, n_max, 10)
    np.testing.assert_array_equal(np.asarray(corpus.bases),
                                  [s.base for s in eng.shards])
    np.testing.assert_array_equal(
        np.asarray(corpus.n_rows),
        [int(s.index.x.shape[0]) for s in eng.shards])
    kw = dict(k=5, ef=24, variant="acorn-gamma", m=8, m_beta=16)
    for s, shard in enumerate(eng.shards):
        gp, xp = shard_slice(corpus, s)
        n_s = int(shard.index.x.shape[0])
        # padded vector rows are zero-filled, real rows untouched
        np.testing.assert_array_equal(np.asarray(xp)[:n_s],
                                      np.asarray(shard.index.x))
        assert (np.asarray(xp)[n_s:] == 0).all()
        masks = np.asarray(evaluate_batch(wl.predicates, shard.index.table))
        padded = np.zeros((masks.shape[0], n_max), bool)
        padded[:, :n_s] = masks
        i1, d1, st1 = hybrid_search(shard.index.graph, shard.index.x, wl.xq,
                                    jnp.asarray(masks), **kw)
        i2, d2, st2 = hybrid_search(gp, xp, wl.xq, jnp.asarray(padded), **kw)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))
        np.testing.assert_array_equal(np.asarray(st1.dist_comps),
                                      np.asarray(st2.dist_comps))
        np.testing.assert_array_equal(np.asarray(st1.hops),
                                      np.asarray(st2.hops))


def test_corpus_search_batch_empty_batch():
    """Zero queries return (0, k) / (S, 0) shapes instead of crashing on
    np.concatenate([]) — the same empty-input crash class PR 2 fixed in
    the serving engine."""
    from repro.core import ExecutionSpec, VariantCache, compile_predicates
    from repro.core.predicates import Equals
    from repro.distributed import corpus_search_batch, stack_regex_aux
    ds = make_lcps_dataset(n=300, d=8, card=4, seed=0)
    acorn = AcornConfig(M=8, gamma=4, m_beta=16, ef_search=16)
    eng = ServingEngine(ds.x, ds.table, acorn,
                        EngineConfig(batch_size=8, k=5, n_shards=2))
    tables = [s.index.table for s in eng.shards]
    corpus = stack_corpus([s.index.graph for s in eng.shards],
                          [s.index.x for s in eng.shards],
                          [s.base for s in eng.shards], tables=tables)
    n_max = int(corpus.x.shape[1])
    # an empty-row program: compile one predicate, slice zero rows
    prog = compile_predicates([Equals("label", 0)], ds.table).take(
        np.arange(0))
    aux = stack_regex_aux(tables, n_max, prog.regex_leaves)
    z = jnp.zeros
    ids, d, dcs, hps = corpus_search_batch(
        corpus, z((0, 8)), prog, aux, z((2, 0, 5), jnp.int32),
        z((2, 0, 5)), z((2, 0), bool), jnp.ones((2,), bool),
        k=5, ef=16, variant="acorn-gamma", m=8, m_beta=16, metric="l2",
        compressed_level0=True, max_expansions=64,
        spec=ExecutionSpec(data_parallel=1, corpus_parallel=2),
        buckets=(8,), cache=VariantCache())
    assert ids.shape == (0, 5) and d.shape == (0, 5)
    assert dcs.shape == (2, 0) and hps.shape == (2, 0)


def test_corpus_search_batch_requires_columns():
    """A corpus stacked without attribute tables cannot evaluate predicate
    programs in-program — it must fail loudly, not silently return
    unfiltered results."""
    from repro.core import ExecutionSpec, VariantCache, compile_predicates
    from repro.core.predicates import Equals
    from repro.distributed import corpus_search_batch
    ds = make_lcps_dataset(n=300, d=8, card=4, seed=0)
    acorn = AcornConfig(M=8, gamma=4, m_beta=16, ef_search=16)
    eng = ServingEngine(ds.x, ds.table, acorn,
                        EngineConfig(batch_size=8, k=5, n_shards=2))
    corpus = stack_corpus([s.index.graph for s in eng.shards],
                          [s.index.x for s in eng.shards],
                          [s.base for s in eng.shards])  # no tables
    assert corpus.columns is None
    prog = compile_predicates([Equals("label", 0)], ds.table)
    n_max = int(corpus.x.shape[1])
    with pytest.raises(ValueError, match="without attribute tables"):
        corpus_search_batch(
            corpus, jnp.zeros((1, 8)), prog,
            jnp.zeros((2, 1, n_max), bool), jnp.zeros((2, 1, 5), jnp.int32),
            jnp.zeros((2, 1, 5)), jnp.zeros((2, 1), bool),
            jnp.ones((2,), bool), k=5, ef=16, variant="acorn-gamma", m=8,
            m_beta=16, metric="l2", compressed_level0=True,
            max_expansions=64,
            spec=ExecutionSpec(data_parallel=1, corpus_parallel=2),
            buckets=(8,), cache=VariantCache())


def test_search_batch_rejects_multi_shard_corpus_parallel():
    """search_batch searches one corpus shard; the knob is key-threading
    only and a multi-shard request must fail loudly, not silently search
    an unsharded graph."""
    from repro.core import (ExecutionSpec, VariantCache, build_acorn_gamma,
                            search_batch)
    ds = make_lcps_dataset(n=300, d=8, card=4, seed=0)
    wl = make_workload(ds, kind="equals", n_queries=4, k=3, seed=1, card=4)
    g = build_acorn_gamma(ds.x, jax.random.PRNGKey(0), M=8, gamma=4,
                          m_beta=16)
    kw = dict(k=3, ef=8, variant="acorn-gamma", m=8, m_beta=16, buckets=(4,))
    with pytest.raises(ValueError):
        search_batch(g, ds.x, wl.xq, wl.masks(ds),
                     spec=ExecutionSpec(corpus_parallel=2), **kw)
    cache = VariantCache()
    search_batch(g, ds.x, wl.xq, wl.masks(ds), cache=cache,
                 spec=ExecutionSpec(corpus_parallel=1), **kw)
    # the resolved ExecutionSpec terminates the key; single-shard pins cp=1
    assert all(key[-1].corpus_parallel == 1 for key in cache.fns)


# ---------------------------------------------------------------------------
# subprocess: 8-device mesh — SPMD vs host oracle + fault injection
# ---------------------------------------------------------------------------

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
assert jax.local_device_count() == 8

from repro.core import AcornConfig, ExecutionSpec, recall_at_k
from repro.data import make_lcps_dataset, make_workload
from repro.serve import EngineConfig, ServingEngine

ds = make_lcps_dataset(n=1200, d=12, card=6, seed=0)
wl = make_workload(ds, kind="equals", n_queries=37, k=10, seed=1, card=6)
GT = wl.gt(ds)
BS = 16

# ---- no host-side mask materialization on the serving path ----
# Predicates now travel as compiled programs evaluated in-program against
# shard-resident columns (SPMD) or through the fused plan evaluator (host
# oracle).  Forbid the legacy per-predicate host evaluators outright: any
# serving-path call would crash every parity block below.
import repro.core.predicates as _pred_mod
def _forbidden(*a, **k):
    raise RuntimeError("legacy host-side predicate evaluation on serving path")
_pred_mod.evaluate_batch = _forbidden
_pred_mod.evaluate = _forbidden

def serve_host(eng, xq, preds):
    outs_i, outs_d = [], []
    for s in range(0, xq.shape[0], BS):
        i, d = eng.search_batch_host(xq[s:s + BS], list(preds[s:s + BS]))
        outs_i.append(np.asarray(i)); outs_d.append(np.asarray(d))
    return np.concatenate(outs_i), np.concatenate(outs_d)

def assert_parity(eng, tag):
    ids_s, d_s = eng.serve(wl.xq, wl.predicates)
    ids_h, d_h = serve_host(eng, wl.xq, wl.predicates)
    np.testing.assert_array_equal(np.asarray(ids_s), ids_h, err_msg=tag)
    np.testing.assert_array_equal(np.asarray(d_s), d_h, err_msg=tag)
    # regression: SPMD results must survive FURTHER traced ops.  Before
    # corpus_search_batch materialized its outputs, the mesh program's
    # replicated-claim output sharding could turn a downstream traced op
    # (serve()'s jnp.concatenate) into a cross-replica sum — ids exactly
    # x n_shards — depending on compile context, so a parity check alone
    # passed in one run order and corrupted in another.
    cat = jnp.concatenate([ids_s, ids_s])
    np.testing.assert_array_equal(np.asarray(cat)[: ids_s.shape[0]],
                                  np.asarray(ids_s), err_msg=tag)
    return np.asarray(ids_s), np.asarray(d_s)

# ---- every (data, corpus) shape of the 8-device mesh, bit-identical ----
for dp, cp in [(2, 4), (4, 2), (1, 8), (8, 1)]:
    acorn = AcornConfig(M=8, gamma=6, m_beta=16, ef_search=32,
                        buckets=(16, 64))
    eng = ServingEngine(ds.x, ds.table, acorn,
                        EngineConfig(batch_size=BS, k=10, n_shards=cp,
                                     spec=ExecutionSpec(data_parallel=dp,
                                                        corpus_parallel=cp)))
    assert eng.spmd_mesh_shape() == (dp, cp), eng.spmd_mesh_shape()
    ids_m, _ = assert_parity(eng, f"mesh {dp}x{cp}")
    # absolute quality guard (parity alone can't catch a bug both paths
    # share): the SPMD results must actually be good neighbors
    r = float(recall_at_k(jnp.asarray(ids_m), GT))
    assert r > 0.9, (dp, cp, r)
    # steady state: one trace per jit bucket, repeats mint nothing
    assert eng.spmd_traces() == {16: 1}, eng.spmd_traces()
    eng.serve(wl.xq, wl.predicates)
    assert eng.spmd_traces() == {16: 1}, eng.spmd_traces()
    # keys end (..., program_shape_sig, resolved ExecutionSpec, "corpus")
    for k in eng.spmd_cache.fns:
        assert k[-1] == "corpus"
        assert k[-2].corpus_parallel == cp and k[-2].data_parallel == dp
        assert isinstance(k[-3], tuple)  # bucketed program shape signature

# ---- auto geometry: corpus_parallel=None picks (ndev//n_shards, n_shards)
acorn = AcornConfig(M=8, gamma=6, m_beta=16, ef_search=32, buckets=(16, 64),
                    data_parallel=0)
eng = ServingEngine(ds.x, ds.table, acorn,
                    EngineConfig(batch_size=BS, k=10, n_shards=2))
assert eng.spmd_mesh_shape() == (4, 2), eng.spmd_mesh_shape()
assert_parity(eng, "auto mesh")

# ---- fault injection: mirrored failover (duplicate dispatch) ----
acorn = AcornConfig(M=8, gamma=6, m_beta=16, ef_search=32, buckets=(16, 64))
mesh24 = ExecutionSpec(data_parallel=2, corpus_parallel=4)
eng = ServingEngine(ds.x, ds.table, acorn,
                    EngineConfig(batch_size=BS, k=10, n_shards=4,
                                 spec=mesh24, duplicate_dispatch=True))
assert eng.spmd_mesh_shape() == (2, 4)
ids0, d0 = assert_parity(eng, "mirrored healthy")
assert eng.stats["duplicated_dispatches"] == 0
eng.fail_shard(0)
ids1, d1 = assert_parity(eng, "mirrored shard-0 down")
# mirror answered: results unchanged despite the failed primary, and the
# duplicate work is accounted (once per batch per failed shard, both paths)
np.testing.assert_array_equal(ids0, ids1)
np.testing.assert_array_equal(d0, d1)
assert eng.stats["duplicated_dispatches"] > 0
# rebuild restores a healthy primary, restacks the mesh corpus, and the
# duplicate-dispatch counter stops moving
eng.rebuild_shard(0)
before = eng.stats["duplicated_dispatches"]
ids2, _ = assert_parity(eng, "rebuilt")
np.testing.assert_array_equal(ids0, ids2)
assert eng.stats["duplicated_dispatches"] == before

# ---- fault injection: hard loss without mirrors ----
eng = ServingEngine(ds.x, ds.table, acorn,
                    EngineConfig(batch_size=BS, k=10, n_shards=4,
                                 spec=mesh24,
                                 duplicate_dispatch=False))
healthy_ids, _ = assert_parity(eng, "unmirrored healthy")
eng.fail_shard(1)
ids_l, d_l = assert_parity(eng, "unmirrored shard-1 down")
# the dead shard's global-id range vanished from the results
lo = eng.shards[1].base
hi = eng.shards[2].base
valid = ids_l[ids_l >= 0]
assert not ((valid >= lo) & (valid < hi)).any()
# no mirror ran -> the straggler stat must not claim a duplicate dispatch
assert eng.stats["duplicated_dispatches"] == 0
# every shard down degrades to all -1 / inf on both paths
for s in range(4):
    eng.fail_shard(s)
ids_e, d_e = assert_parity(eng, "all down")
assert (ids_e == -1).all() and np.isinf(d_e).all()
for s in range(4):
    eng.rebuild_shard(s)
ids_r, _ = assert_parity(eng, "all rebuilt")
np.testing.assert_array_equal(ids_r, healthy_ids)
assert eng.stats["duplicated_dispatches"] == 0

print("CORPUS_PARALLEL_OK")
"""


def test_corpus_sharded_spmd_parity_and_faults_8dev():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "CORPUS_PARALLEL_OK" in r.stdout
