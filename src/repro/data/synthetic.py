"""Synthetic hybrid-search datasets reproducing the paper's workload axes.

Two families mirroring §7.1:

* LCPS (SIFT1M/Paper-style): random attribute int in [0, card); equality
  predicates; predicate-set cardinality = card (12 in the paper).
* HCPS (TripClick/LAION-style): Gaussian-mixture vectors with
  *predicate clustering* — each cluster carries its own keyword set — plus a
  date column and a caption string column.  Query workloads control the
  paper's three correlation regimes (Figure 2): keywords of the query's own
  cluster (pos-cor), keywords of a far cluster (neg-cor), or random keywords
  (no-cor), and optionally date-range and regex predicates.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bruteforce import ground_truth
from repro.core.predicates import (AttributeTable, Between, ContainsAny,
                                   Equals, Predicate, RegexMatch, evaluate,
                                   evaluate_batch, pack_multihot)

KEYWORD_NAMES = [
    "animal", "scary", "green", "blue", "red", "vintage", "portrait", "city",
    "nature", "food", "car", "beach", "night", "snow", "art", "music",
    "sport", "baby", "dog", "cat", "flower", "mountain", "ocean", "forest",
    "sunset", "abstract", "retro", "neon", "minimal", "cozy",
]


@dataclass
class Dataset:
    x: jax.Array                       # (n, d) float32
    table: AttributeTable
    cluster_of: Optional[np.ndarray] = None   # (n,) int
    centers: Optional[np.ndarray] = None      # (C, d)
    cluster_keywords: Optional[np.ndarray] = None  # (C, kw_per_cluster)
    name: str = "synthetic"

    @property
    def n(self) -> int:
        return int(self.x.shape[0])

    @property
    def d(self) -> int:
        return int(self.x.shape[1])


@dataclass
class Workload:
    xq: jax.Array                      # (B, d)
    predicates: List[Predicate]
    k: int = 10
    name: str = "workload"
    _gt: Optional[jax.Array] = field(default=None, repr=False)
    _masks: Optional[jax.Array] = field(default=None, repr=False)

    def masks(self, ds: Dataset) -> jax.Array:
        if self._masks is None:
            self._masks = evaluate_batch(self.predicates, ds.table)
        return self._masks

    def gt(self, ds: Dataset) -> jax.Array:
        if self._gt is None:
            self._gt = ground_truth(self.xq, ds.x, self.masks(ds), self.k)
        return self._gt

    def avg_selectivity(self, ds: Dataset) -> float:
        return float(jnp.mean(jnp.mean(self.masks(ds).astype(jnp.float32),
                                       axis=1)))


# ---------------------------------------------------------------------------


def make_lcps_dataset(n: int = 20000, d: int = 32, card: int = 12,
                      seed: int = 0, clustered: bool = True,
                      center_scale: float = 1.2) -> Dataset:
    """center_scale controls cluster separation.  The default (1.2 with unit
    within-cluster noise) gives overlapping, manifold-like clusters — the
    regime of the paper's real datasets (SIFT/CLIP/DPR embeddings).  Scores
    >= 2.5 produce isolated 'atolls' whose predicate subgraphs fragment; the
    paper's connectivity analysis (§6.3.1) explicitly excludes that regime
    and benchmarks/fig13 documents it."""
    rng = np.random.default_rng(seed)
    if clustered:
        n_c = 32
        centers = rng.normal(size=(n_c, d)).astype(np.float32) * center_scale
        cluster_of = rng.integers(0, n_c, size=n)
        x = centers[cluster_of] + rng.normal(size=(n, d)).astype(np.float32)
    else:
        centers, cluster_of = None, None
        x = rng.normal(size=(n, d)).astype(np.float32)
    # balanced label assignment (selectivity exactly 1/card, matching the
    # paper's uniform-random expectation; equal-size oracle partitions also
    # share one jit cache entry instead of card distinct shapes)
    attr = rng.permutation(np.arange(n) % card).astype(np.int32)
    table = AttributeTable(int_cols={"label": jnp.asarray(attr)},
                           bitset_cols={}, str_cols={}, n_keywords={})
    return Dataset(x=jnp.asarray(x), table=table, cluster_of=cluster_of,
                   centers=centers, name=f"lcps{n}")


def make_hcps_dataset(n: int = 20000, d: int = 32, n_clusters: int = 0,
                      kw_per_cluster: int = 3, n_keywords: int = 30,
                      date_range: int = 120, seed: int = 0,
                      center_scale: float = 1.5,
                      noise_kw_prob: float = 0.5) -> Dataset:
    """Gaussian mixture with cluster-correlated keyword sets (predicate
    clustering per Figure 2) + a date column + caption strings.  Clusters
    overlap (center_scale 1.5 vs unit noise) as in real embedding manifolds;
    noise keywords give every region nonzero passing density, mirroring how
    CLIP keyword lists mix across LAION image clusters."""
    rng = np.random.default_rng(seed)
    if n_clusters <= 0:
        # real corpora add content modes with scale rather than inflating
        # existing ones: keep ~256 rows per cluster so graph-radius vs
        # cluster-size geometry is n-invariant (generator note, DESIGN §2)
        n_clusters = max(12, n // 256)
    centers = rng.normal(size=(n_clusters, d)).astype(np.float32) * center_scale
    cluster_of = rng.integers(0, n_clusters, size=n)
    x = centers[cluster_of] + rng.normal(size=(n, d)).astype(np.float32)

    cluster_kws = np.stack([
        rng.choice(n_keywords, size=kw_per_cluster, replace=False)
        for _ in range(n_clusters)
    ])
    kw_lists, captions = [], []
    for i in range(n):
        kws = list(cluster_kws[cluster_of[i]])
        if rng.random() < noise_kw_prob:
            kws.append(int(rng.integers(0, n_keywords)))
        kw_lists.append(kws)
        captions.append("photo of " + " ".join(KEYWORD_NAMES[k] for k in kws))
    bits = pack_multihot(kw_lists, n_keywords)
    dates = rng.integers(0, date_range, size=n).astype(np.int32)

    table = AttributeTable(
        int_cols={"date": jnp.asarray(dates)},
        bitset_cols={"keywords": jnp.asarray(bits)},
        str_cols={"caption": np.asarray(captions, dtype=object)},
        n_keywords={"keywords": n_keywords},
    )
    return Dataset(x=jnp.asarray(x), table=table, cluster_of=cluster_of,
                   centers=centers, cluster_keywords=cluster_kws,
                   name=f"hcps{n}")


# ---------------------------------------------------------------------------


def _far_cluster(centers: np.ndarray, c: int) -> int:
    d = np.sum((centers - centers[c]) ** 2, axis=1)
    return int(np.argmax(d))


def make_workload(
    ds: Dataset,
    kind: str = "equals",
    correlation: str = "none",
    n_queries: int = 64,
    k: int = 10,
    seed: int = 1,
    card: int = 12,
    date_width: int = 30,
    range_column: str = "date",
    value_range: int = 120,
) -> Workload:
    """Build a query workload over ``ds``.

    kind: 'equals' (LCPS), 'contains', 'between', 'contains+between',
          'regex' (HCPS).
    correlation: 'none' | 'pos' | 'neg' — matches Figure 2 / §7.1.2. Only
          meaningful for 'contains' on clustered HCPS data.
    'between' draws ``Between(range_column, lo, lo + date_width)`` with
    ``lo`` uniform in ``[0, value_range - date_width)`` — HCPS dates by
    default; ``range_column="label", value_range=card`` gives LCPS a wider
    predicate than equality.
    """
    rng = np.random.default_rng(seed)
    n, d = ds.n, ds.d
    qi = rng.integers(0, n, size=n_queries)
    xq = np.asarray(ds.x)[qi] + 0.1 * rng.normal(size=(n_queries, d)).astype(
        np.float32)

    preds: List[Predicate] = []
    if kind == "equals":
        for _ in range(n_queries):
            preds.append(Equals("label", int(rng.integers(0, card))))
    elif kind in ("contains", "contains+between", "between", "regex"):
        assert ds.cluster_keywords is not None or kind == "between"
        for i in range(n_queries):
            qc = int(ds.cluster_of[qi[i]])
            if kind == "between":
                lo = int(rng.integers(0, value_range - date_width))
                preds.append(Between(range_column, lo, lo + date_width))
                continue
            if correlation == "pos":
                kws = ds.cluster_keywords[qc]
            elif correlation == "neg":
                kws = ds.cluster_keywords[_far_cluster(ds.centers, qc)]
            else:
                rc = int(rng.integers(0, len(ds.cluster_keywords)))
                kws = ds.cluster_keywords[rc]
            kws = tuple(int(w) for w in kws[: rng.integers(1, len(kws) + 1)])
            if kind == "regex":
                word = KEYWORD_NAMES[kws[0]]
                preds.append(RegexMatch("caption", rf"\b{word}\b"))
            else:
                p: Predicate = ContainsAny("keywords", kws)
                if kind == "contains+between":
                    lo = int(rng.integers(0, 120 - date_width))
                    p = p & Between("date", lo, lo + date_width)
                preds.append(p)
    else:
        raise ValueError(kind)

    name = f"{kind}-{correlation}" if correlation != "none" else kind
    return Workload(xq=jnp.asarray(xq), predicates=preds, k=k, name=name)
