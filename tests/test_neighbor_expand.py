"""Parity matrix for the fused neighbor-expansion kernel.

Three implementations must agree bit-for-bit on every input:

  * ``neighbor_expand_argsort`` — the legacy argsort-dedup formulation
    (the behaviour ``get_neighbors`` shipped with, kept as the oracle);
  * ``neighbor_expand_ref``     — the sort-free jnp path (the default);
  * ``neighbor_expand`` with ``use_kernel=True`` — the Pallas kernel in
    interpret mode.

The matrix covers the edge cases the fusion bends around: ``m_beta=0`` /
``m_beta=cap`` (empty head / empty tail), all-predicate-fail lanes,
fully-visited lanes, duplicate-heavy neighbor rows, absent-level ids, and
``pass_mask`` / ``visited`` of ``None``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import neighbor_rows
from repro.core.search import get_neighbors
from repro.data import make_lcps_dataset
from repro.kernels.neighbor_expand import (neighbor_expand,
                                           neighbor_expand_argsort,
                                           neighbor_expand_ref)
from repro.kernels.neighbor_expand.kernel import (neighbor_expand_packed,
                                                  pack_bitmap)

KEY = jax.random.PRNGKey(0)
STRATEGIES = ["filter", "compress", "two_hop"]


def make_case(seed, n=160, n_l=120, cap=10, b=4, dup_heavy=False,
              all_fail=False, all_visited=False):
    """Random level: pos maps a subset of global ids to table rows."""
    rng = np.random.default_rng(seed)
    pos = np.full(n, -1, np.int32)
    members = rng.choice(n, size=n_l, replace=False)
    pos[members] = np.arange(n_l)
    tbl = rng.choice(members, size=(n_l, cap)).astype(np.int32)
    tbl[rng.random((n_l, cap)) < 0.25] = -1
    row = rng.choice(members, size=(b, cap)).astype(np.int32)
    row[rng.random((b, cap)) < 0.25] = -1
    # a few ids that are valid globally but absent from the level
    absent = np.setdiff1d(np.arange(n), members)
    if len(absent):
        row[:, 0] = rng.choice(absent, size=b)
    if dup_heavy:
        row[:, cap // 2:] = row[:, :cap - cap // 2]
        tbl[:, cap // 2:] = tbl[:, :cap - cap // 2]
    pm = np.zeros((b, n), bool) if all_fail else rng.random((b, n)) < 0.6
    vis = (np.ones((b, n), bool) if all_visited
           else rng.random((b, n)) < 0.15)
    return (jnp.asarray(row), jnp.asarray(tbl), jnp.asarray(pos),
            jnp.asarray(pm), jnp.asarray(vis))


def assert_all_equal(row, tbl, pos, pm, vis, strategy, m, m_beta):
    want = neighbor_expand_argsort(row, tbl, pos, pm, vis, strategy=strategy,
                                   m=m, m_beta=m_beta)
    ref = neighbor_expand_ref(row, tbl, pos, pm, vis, strategy=strategy,
                              m=m, m_beta=m_beta)
    kern = neighbor_expand(row, tbl, pos, pm, vis, strategy=strategy, m=m,
                           m_beta=m_beta, use_kernel=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(kern), np.asarray(want))


@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("m_beta_kind", ["zero", "mid", "cap"])
def test_parity_m_beta_edges(strategy, m_beta_kind):
    cap = 10
    m_beta = {"zero": 0, "mid": cap // 2, "cap": cap}[m_beta_kind]
    case = make_case(seed=cap + m_beta, cap=cap)
    assert_all_equal(*case, strategy=strategy, m=8, m_beta=m_beta)


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_parity_all_predicate_fail(strategy):
    row, tbl, pos, pm, vis = make_case(seed=7, all_fail=True)
    assert_all_equal(row, tbl, pos, pm, vis, strategy=strategy, m=8, m_beta=4)
    out = neighbor_expand(row, tbl, pos, pm, vis, strategy=strategy, m=8,
                          m_beta=4, use_kernel=True, interpret=True)
    assert (np.asarray(out) == -1).all()


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_parity_fully_visited(strategy):
    row, tbl, pos, pm, vis = make_case(seed=8, all_visited=True)
    assert_all_equal(row, tbl, pos, pm, vis, strategy=strategy, m=8, m_beta=4)
    out = neighbor_expand(row, tbl, pos, pm, vis, strategy=strategy, m=8,
                          m_beta=4, use_kernel=True, interpret=True)
    assert (np.asarray(out) == -1).all()


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_parity_duplicate_heavy_rows(strategy):
    case = make_case(seed=9, dup_heavy=True)
    assert_all_equal(*case, strategy=strategy, m=6, m_beta=3)


@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("has_pm,has_vis", [(False, True), (True, False),
                                            (False, False)])
def test_parity_none_masks(strategy, has_pm, has_vis):
    row, tbl, pos, pm, vis = make_case(seed=10)
    pm = pm if has_pm else None
    vis = vis if has_vis else None
    assert_all_equal(row, tbl, pos, pm, vis, strategy=strategy, m=8, m_beta=4)


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_parity_m_wider_than_candidates(strategy):
    """m larger than the whole candidate stream: all survivors + -1 pad."""
    case = make_case(seed=11, cap=4, n=60, n_l=40)
    assert_all_equal(*case, strategy=strategy, m=64, m_beta=2)


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_packed_entry_point_updates_visited(strategy):
    """neighbor_expand_packed returns the same ids from packed bitmaps and
    the visited bitmap with exactly those ids set (the beam's update)."""
    row, tbl, pos, pm, vis = make_case(seed=11, dup_heavy=True)
    want = neighbor_expand_ref(row, tbl, pos, pm, vis, strategy=strategy,
                               m=8, m_beta=4)
    ids, vis_out = neighbor_expand_packed(
        row, tbl, pos, pack_bitmap(pm), pack_bitmap(vis), strategy=strategy,
        m=8, m_beta=4, interpret=True)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(want))
    safe = jnp.clip(want, 0, vis.shape[1] - 1)
    vis_want = vis.at[jnp.arange(vis.shape[0])[:, None], safe].max(want >= 0)
    np.testing.assert_array_equal(np.asarray(vis_out),
                                  np.asarray(pack_bitmap(vis_want)))


def test_first_occurrence_keeps_scan_order():
    """Hand-checkable: dedup keeps first occurrences in candidate order."""
    row = jnp.asarray([[5, 3, 5, 2]], jnp.int32)
    tbl = jnp.full((6, 4), -1, jnp.int32)
    pos = jnp.arange(6, dtype=jnp.int32)
    out = neighbor_expand(row, tbl, pos, None, None, strategy="two_hop",
                          m=4, m_beta=0)
    np.testing.assert_array_equal(np.asarray(out), [[5, 3, 2, -1]])
    kern = neighbor_expand(row, tbl, pos, None, None, strategy="two_hop",
                           m=4, m_beta=0, use_kernel=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(kern), [[5, 3, 2, -1]])


@pytest.mark.parametrize("strategy", ["compress", "two_hop"])
def test_parity_large_n_argsort_branch(strategy):
    """n >> C flips the ref's trace-time dedup choice to the n-independent
    argsort (the scatter tile would dominate at index scale); results must
    stay identical and the branch predicate must actually flip."""
    from repro.kernels.neighbor_expand import use_scatter_dedup
    case = make_case(seed=13, n=4096, n_l=64, cap=4)
    c_max = 4 + 4 * 5   # two_hop/compress candidate count at cap=4
    assert not use_scatter_dedup(4096, c_max)
    assert use_scatter_dedup(160, c_max)
    assert_all_equal(*case, strategy=strategy, m=6, m_beta=2)


def test_empty_batch_and_zero_m():
    row, tbl, pos, pm, vis = make_case(seed=12)
    out = neighbor_expand(row[:0], tbl, pos, None, None, strategy="filter",
                          m=8)
    assert out.shape == (0, 8)
    out = neighbor_expand(row, tbl, pos, None, None, strategy="compress",
                          m=0, m_beta=4)
    assert out.shape == (row.shape[0], 0)


# ---------------------------------------------------------------------------
# get_neighbors integration (pass_mask=None fix + kernel routing)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def graph_ds():
    ds = make_lcps_dataset(n=800, d=8, card=8, seed=0)
    from repro.core.build import build_acorn_gamma
    return ds, build_acorn_gamma(ds.x, KEY, M=8, gamma=8, m_beta=16)


@pytest.mark.parametrize("strategy", ["plain", "filter", "compress",
                                      "two_hop"])
def test_get_neighbors_accepts_none_mask(graph_ds, strategy):
    """Every strategy accepts pass_mask=None = all nodes pass (the
    unfiltered substrate) — previously only 'plain' survived a None mask."""
    ds, g = graph_ds
    c = jnp.asarray(17, jnp.int32)
    out = get_neighbors(g, 0, c, None, strategy, 8, 16)
    out = np.asarray(out)
    if strategy == "plain":
        assert out.shape == (g.cap(0),)
        return
    assert out.shape == (8,)
    # with an all-true mask the result must be identical
    all_true = jnp.ones((ds.x.shape[0],), bool)
    with_mask = np.asarray(get_neighbors(g, 0, c, all_true, strategy, 8, 16))
    np.testing.assert_array_equal(out, with_mask)
    # -1 padding discipline: valid ids first, then -1
    valid = out >= 0
    assert not (~valid[:-1] & valid[1:]).any()


def test_get_neighbors_none_mask_respects_visited(graph_ds):
    ds, g = graph_ds
    c = jnp.asarray(5, jnp.int32)
    base = np.asarray(get_neighbors(g, 0, c, None, "filter", 8, 16))
    first = base[0]
    assert first >= 0
    visited = jnp.zeros((ds.x.shape[0],), bool).at[first].set(True)
    out = np.asarray(get_neighbors(g, 0, c, None, "filter", 8, 16,
                                   visited=visited))
    assert first not in out


@pytest.mark.parametrize("strategy", ["filter", "compress", "two_hop"])
def test_get_neighbors_kernel_matches_ref(graph_ds, strategy):
    ds, g = graph_ds
    rng = np.random.default_rng(3)
    pm = jnp.asarray(rng.random(ds.x.shape[0]) < 0.5)
    c = jnp.asarray(42, jnp.int32)
    ref = get_neighbors(g, 0, c, pm, strategy, 8, 16)
    kern = get_neighbors(g, 0, c, pm, strategy, 8, 16, use_kernel=True,
                         interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(kern))


def test_hybrid_search_expand_kernel_knob(graph_ds):
    """expand_kernel alone (gather_distance ref + expansion kernel) returns
    identical results to the all-ref path."""
    from repro.core import hybrid_search
    ds, g = graph_ds
    rng = np.random.default_rng(4)
    xq = jnp.asarray(rng.normal(size=(4, ds.x.shape[1])), jnp.float32)
    labels = np.asarray(ds.table.int_cols["label"])
    masks = jnp.asarray(labels[None, :] == np.arange(4)[:, None] % 8)
    kw = dict(k=5, ef=24, variant="acorn-gamma", m=8, m_beta=16)
    from repro.core import ExecutionSpec
    ids0, d0, st0 = hybrid_search(g, ds.x, xq, masks, **kw)
    ids1, d1, st1 = hybrid_search(g, ds.x, xq, masks,
                                  spec=ExecutionSpec(expand_kernel=True,
                                                     interpret=True),
                                  **kw)
    np.testing.assert_array_equal(np.asarray(ids0), np.asarray(ids1))
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
    np.testing.assert_array_equal(np.asarray(st0.dist_comps),
                                  np.asarray(st1.dist_comps))
