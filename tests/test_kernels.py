"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # property tests degrade to skips when hypothesis is absent
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised on minimal installs
    HAVE_HYPOTHESIS = False

from repro.kernels import (bounded_sorted_merge, bounded_sorted_merge_ref,
                           embedding_bag, filtered_topk, gather_distance,
                           pna_aggregate)
from repro.kernels.embedding_bag.ref import (embedding_bag_ref,
                                             embedding_bag_segment_ref)
from repro.kernels.filtered_topk.ref import filtered_topk_ref
from repro.kernels.gather_distance.ref import gather_distance_ref
from repro.kernels.pna_aggregate.ref import (pna_aggregate_ref,
                                             pna_aggregate_segment_ref)

RNG = np.random.default_rng(42)


# ---------------------------------------------------------------------------
# filtered_topk
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,n,d,k", [
    (1, 100, 8, 5), (4, 513, 32, 10), (9, 1024, 128, 16), (130, 300, 16, 3),
])
def test_filtered_topk_shapes(b, n, d, k):
    q = jnp.asarray(RNG.normal(size=(b, d)), jnp.float32)
    x = jnp.asarray(RNG.normal(size=(n, d)), jnp.float32)
    mask = jnp.asarray(RNG.random((b, n)) < 0.5)
    ids, dd = filtered_topk(q, x, mask, k, interpret=True)
    rids, rd = filtered_topk_ref(q, x, mask, k)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(rids))
    np.testing.assert_allclose(np.asarray(dd), np.asarray(rd), atol=2e-3)


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_filtered_topk_metrics(metric):
    q = jnp.asarray(RNG.normal(size=(3, 16)), jnp.float32)
    x = jnp.asarray(RNG.normal(size=(257, 16)), jnp.float32)
    mask = jnp.ones((3, 257), bool)
    ids, _ = filtered_topk(q, x, mask, 7, metric=metric, interpret=True)
    rids, _ = filtered_topk_ref(q, x, mask, 7, metric=metric)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(rids))


def test_filtered_topk_empty_mask_rows():
    q = jnp.asarray(RNG.normal(size=(2, 8)), jnp.float32)
    x = jnp.asarray(RNG.normal(size=(64, 8)), jnp.float32)
    mask = jnp.zeros((2, 64), bool).at[1, 5].set(True)
    ids, _ = filtered_topk(q, x, mask, 4, interpret=True)
    ids = np.asarray(ids)
    assert (ids[0] == -1).all()
    assert ids[1, 0] == 5 and (ids[1, 1:] == -1).all()


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(b=st.integers(1, 6), n=st.integers(8, 400), k=st.integers(1, 8),
           p=st.floats(0.05, 0.95))
    def test_filtered_topk_property(b, n, k, p):
        rng = np.random.default_rng(b * 1000 + n)
        q = jnp.asarray(rng.normal(size=(b, 8)), jnp.float32)
        x = jnp.asarray(rng.normal(size=(n, 8)), jnp.float32)
        mask = jnp.asarray(rng.random((b, n)) < p)
        ids, _ = filtered_topk(q, x, mask, k, interpret=True)
        rids, _ = filtered_topk_ref(q, x, mask, k)
        np.testing.assert_array_equal(np.asarray(ids), np.asarray(rids))
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_filtered_topk_property():
        pytest.importorskip("hypothesis")


# ---------------------------------------------------------------------------
# gather_distance
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,m,n,d", [(1, 4, 50, 8), (8, 16, 500, 32),
                                     (3, 33, 128, 128)])
def test_gather_distance_shapes(b, m, n, d):
    ids = jnp.asarray(RNG.integers(-1, n, size=(b, m)), jnp.int32)
    q = jnp.asarray(RNG.normal(size=(b, d)), jnp.float32)
    x = jnp.asarray(RNG.normal(size=(n, d)), jnp.float32)
    got = gather_distance(ids, q, x, interpret=True)
    want = gather_distance_ref(ids, q, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_gather_distance_metric(metric):
    ids = jnp.asarray(RNG.integers(0, 60, size=(2, 5)), jnp.int32)
    q = jnp.asarray(RNG.normal(size=(2, 12)), jnp.float32)
    x = jnp.asarray(RNG.normal(size=(60, 12)), jnp.float32)
    got = gather_distance(ids, q, x, metric=metric, interpret=True)
    want = gather_distance_ref(ids, q, x, metric=metric)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_gather_distance_kernel_invalid_ids(metric):
    """CI gate for the search pipeline: interpreted Pallas kernel matches the
    jnp reference including INVALID (-1) padding lanes."""
    n, d = 80, 16
    ids = np.asarray(RNG.integers(0, n, size=(4, 9)), np.int32)
    ids[0, :] = -1            # fully-invalid query row
    ids[1, ::2] = -1          # interleaved padding
    ids = jnp.asarray(ids)
    q = jnp.asarray(RNG.normal(size=(4, d)), jnp.float32)
    x = jnp.asarray(RNG.normal(size=(n, d)), jnp.float32)
    got = gather_distance(ids, q, x, metric=metric, use_kernel=True,
                          interpret=True)
    want = gather_distance_ref(ids, q, x, metric=metric)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)
    assert np.isinf(np.asarray(got)[0]).all()


def test_gather_distance_use_kernel_off_is_ref():
    ids = jnp.asarray(RNG.integers(-1, 30, size=(3, 7)), jnp.int32)
    q = jnp.asarray(RNG.normal(size=(3, 8)), jnp.float32)
    x = jnp.asarray(RNG.normal(size=(30, 8)), jnp.float32)
    got = gather_distance(ids, q, x, use_kernel=False)
    want = gather_distance_ref(ids, q, x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_filtered_topk_kernel_padded_masked(metric):
    """Kernel vs ref on inputs that exercise corpus-tile padding (n not a
    multiple of the tile) AND empty / near-empty mask rows."""
    b, n, d, k = 5, 777, 24, 9     # 777 pads to the 512-wide corpus tile
    q = jnp.asarray(RNG.normal(size=(b, d)), jnp.float32)
    x = jnp.asarray(RNG.normal(size=(n, d)), jnp.float32)
    mask = np.asarray(RNG.random((b, n)) < 0.2)
    mask[0, :] = False            # nothing passes
    mask[1, :] = False
    mask[1, 700:] = True          # only rows inside the padded tail tile
    mask = jnp.asarray(mask)
    ids, dd = filtered_topk(q, x, mask, k, metric=metric, use_kernel=True,
                            interpret=True)
    rids, rd = filtered_topk_ref(q, x, mask, k, metric=metric)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(rids))
    finite = np.isfinite(np.asarray(rd))
    np.testing.assert_allclose(np.asarray(dd)[finite],
                               np.asarray(rd)[finite], atol=2e-3)
    assert (np.asarray(ids)[0] == -1).all()


# ---------------------------------------------------------------------------
# bounded_sorted_merge (beam maintenance of the batched search pipeline)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,l,c", [(1, 8, 4), (7, 64, 16), (3, 33, 40)])
def test_bounded_sorted_merge_matches_ref(b, l, c):
    rng = np.random.default_rng(l * 100 + c)
    beam = np.sort(rng.normal(size=(b, l)).astype(np.float32), axis=1)
    cand = rng.normal(size=(b, c)).astype(np.float32)
    bp = (jnp.asarray(rng.integers(0, 999, size=(b, l)), jnp.int32),
          jnp.asarray(rng.random((b, l)) < 0.5))
    cp = (jnp.asarray(rng.integers(0, 999, size=(b, c)), jnp.int32),
          jnp.asarray(rng.random((b, c)) < 0.5))
    got_d, got_p = bounded_sorted_merge(jnp.asarray(beam), jnp.asarray(cand),
                                        bp, cp)
    want_d, want_p = bounded_sorted_merge_ref(jnp.asarray(beam),
                                              jnp.asarray(cand), bp, cp)
    np.testing.assert_array_equal(np.asarray(got_d), np.asarray(want_d))
    for g, w in zip(got_p, want_p):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_bounded_sorted_merge_inf_and_ties():
    """+inf padding and exact ties must follow stable-argsort order: beam
    entries before equal candidates, both sides in insertion order."""
    inf = np.inf
    beam = jnp.asarray([[0.5, 1.0, 1.0, inf, inf]], jnp.float32)
    cand = jnp.asarray([[1.0, 0.5, inf, 1.0]], jnp.float32)
    bp = (jnp.asarray([[10, 11, 12, -1, -1]], jnp.int32),)
    cp = (jnp.asarray([[20, 21, -1, 23]], jnp.int32),)
    got_d, (got_ids,) = bounded_sorted_merge(beam, cand, bp, cp)
    want_d, (want_ids,) = bounded_sorted_merge_ref(beam, cand, bp, cp)
    np.testing.assert_array_equal(np.asarray(got_d), np.asarray(want_d))
    np.testing.assert_array_equal(np.asarray(got_ids), np.asarray(want_ids))
    # explicit expectation: 0.5(beam) 0.5(cand) 1.0,1.0(beam) 1.0(cand)
    np.testing.assert_array_equal(np.asarray(got_ids), [[10, 21, 11, 12, 20]])


# ---------------------------------------------------------------------------
# embedding_bag
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,l,v,d,mode", [
    (1, 1, 10, 4, "sum"), (16, 8, 1000, 32, "sum"), (5, 20, 64, 16, "mean"),
])
def test_embedding_bag_shapes(b, l, v, d, mode):
    ids = jnp.asarray(RNG.integers(-1, v, size=(b, l)), jnp.int32)
    tab = jnp.asarray(RNG.normal(size=(v, d)), jnp.float32)
    got = embedding_bag(ids, tab, mode=mode, interpret=True)
    want = embedding_bag_ref(ids, tab, mode=mode)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_embedding_bag_all_padding():
    ids = jnp.full((2, 4), -1, jnp.int32)
    tab = jnp.asarray(RNG.normal(size=(10, 8)), jnp.float32)
    out = embedding_bag(ids, tab, mode="mean", interpret=True)
    np.testing.assert_allclose(np.asarray(out), 0.0, atol=1e-7)


def test_embedding_bag_grad_matches_ref():
    ids = jnp.asarray(RNG.integers(-1, 50, size=(6, 7)), jnp.int32)
    tab = jnp.asarray(RNG.normal(size=(50, 8)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(8,)), jnp.float32)
    g1 = jax.grad(lambda t: (embedding_bag(ids, t, mode="mean",
                                           interpret=True) @ w).sum())(tab)
    g2 = jax.grad(lambda t: (embedding_bag_ref(ids, t, "mean") @ w).sum())(tab)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-5)


def test_embedding_bag_segment_form_agrees():
    b, l, v, d = 4, 6, 30, 8
    ids = RNG.integers(-1, v, size=(b, l)).astype(np.int32)
    tab = jnp.asarray(RNG.normal(size=(v, d)), jnp.float32)
    flat = jnp.asarray(ids.reshape(-1))
    seg = jnp.asarray(np.repeat(np.arange(b), l))
    got = embedding_bag_segment_ref(flat, seg, tab, b, mode="mean")
    want = embedding_bag_ref(jnp.asarray(ids), tab, "mean")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# ---------------------------------------------------------------------------
# pna_aggregate
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,n,f", [(1, 8, 4), (4, 30, 11), (2, 64, 75)])
def test_pna_aggregate_shapes(b, n, f):
    adj = jnp.asarray((RNG.random((b, n, n)) < 0.3).astype(np.float32))
    feats = jnp.asarray(RNG.normal(size=(b, n, f)), jnp.float32)
    got = pna_aggregate(adj, feats, interpret=True)
    want = pna_aggregate_ref(adj, feats)
    # sqrt of the cancellation noise in ssq/n - mean^2 bounds abs error at
    # ~sqrt(eps)*|h| for degree-1 nodes -> 2e-3 tolerance on the std block
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-3)


def test_pna_isolated_nodes_zero():
    adj = jnp.zeros((1, 5, 5), jnp.float32)
    feats = jnp.asarray(RNG.normal(size=(1, 5, 3)), jnp.float32)
    out = pna_aggregate(adj, feats, interpret=True)
    # std carries the sqrt(eps)=1e-6 regularizer for grad-safety at var=0
    np.testing.assert_allclose(np.asarray(out), 0.0, atol=2e-6)


def test_pna_segment_matches_dense():
    b, n, f = 1, 12, 5
    adj_np = (RNG.random((n, n)) < 0.4).astype(np.float32)
    np.fill_diagonal(adj_np, 0)
    feats = jnp.asarray(RNG.normal(size=(n, f)), jnp.float32)
    dense = pna_aggregate_ref(jnp.asarray(adj_np)[None], feats[None])[0]
    dst, src = np.nonzero(adj_np)  # row=dst receives from col=src
    msgs = feats[jnp.asarray(src)]
    seg = pna_aggregate_segment_ref(msgs, jnp.asarray(dst), n)
    np.testing.assert_allclose(np.asarray(seg), np.asarray(dense), atol=1e-5)
