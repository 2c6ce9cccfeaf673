"""Seeded corpora, queries and arrival schedules for the benchmark's cells.

The two corpus models are copies of the program's ``repro/data/synthetic.py``
LCPS and HCPS generators (ACORN §7.1), vectorised and kept here so that a
change to the program cannot move the yardstick.  Nothing in this module
imports the program.  Predicates are plain dicts, read by the reference
(``reference.py``) and turned into the program's predicate trees by
``serving.py``:

    {"op": "eq", "column": c, "value": v}
    {"op": "between", "column": c, "lo": lo, "hi": hi}      (inclusive)
    {"op": "contains_any", "column": c, "words": [w, ...]}

Every seed draws the same multiset of predicate parameters (labels, ranges,
word counts, correlation regimes) in a seed-chosen order, so that a seed
changes which work is done and not how much.  A mix that names a
``query_seed`` goes further: its blocks of queries are one fixed draw, and
the run's seed only orders the queries within each block.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

KEYWORD_NAMES = [
    "animal", "scary", "green", "blue", "red", "vintage", "portrait", "city",
    "nature", "food", "car", "beach", "night", "snow", "art", "music",
    "sport", "baby", "dog", "cat", "flower", "mountain", "ocean", "forest",
    "sunset", "abstract", "retro", "neon", "minimal", "cozy",
]

BLOCK = 256  # queries drawn per seeded block


@dataclass
class Corpus:
    """A deployment's data as the benchmark made it (host numpy)."""

    x: np.ndarray                      # (n, d) float32
    columns: Dict[str, np.ndarray]     # raw attribute columns
    kinds: Dict[str, str]              # column -> int | keywords | text
    cluster_of: np.ndarray             # (n,) cluster of each row
    centers: np.ndarray                # (C, d)
    cluster_keywords: Optional[np.ndarray] = None   # (C, w) word ids
    normalized: bool = False

    @property
    def n(self) -> int:
        return int(self.x.shape[0])

    @property
    def d(self) -> int:
        return int(self.x.shape[1])


def _vectors(rng, centers, cluster_of, d):
    x = rng.standard_normal((len(cluster_of), d), dtype=np.float32)
    x += centers[cluster_of]
    return x


def lcps(cfg: dict, seed: int) -> Corpus:
    """LCPS (SIFT1M-shaped): a Gaussian mixture with one integer label
    column of ``cardinality`` values, each on exactly n / cardinality rows
    in an order fixed by ``attribute_seed``."""
    n, d = cfg["n"], cfg["d"]
    card = cfg["schema"]["label"]["cardinality"]
    rng = np.random.default_rng(seed)
    centers = (rng.standard_normal((cfg["clusters"], d), dtype=np.float32)
               * np.float32(cfg["center_scale"]))
    cluster_of = rng.integers(0, cfg["clusters"], size=n)
    x = _vectors(rng, centers, cluster_of, d)
    # the label layout does not follow the seed: the program routes each
    # equality query by its sampled selectivity estimate (§5.2), and a
    # layout drawn per seed would move a different share of the traffic
    # onto the pre-filter route in every run
    label = np.random.default_rng(cfg["attribute_seed"]).permutation(
        np.arange(n) % card).astype(np.int32)
    return Corpus(x=x, columns={"label": label}, kinds={"label": "int"},
                  cluster_of=cluster_of, centers=centers)


def hcps(cfg: dict, seed: int) -> Corpus:
    """HCPS (LAION-shaped): a Gaussian mixture whose clusters each carry
    ``keywords_per_cluster`` words of a ``vocabulary``-word list (predicate
    clustering, ACORN Figure 2), half the rows one more random word, a
    date column and a caption built from the row's words.  Rows are
    scaled to unit length when ``normalize`` is set."""
    n, d = cfg["n"], cfg["d"]
    sch = cfg["schema"]
    vocab = sch["keywords"]["vocabulary"]
    per = sch["keywords"]["keywords_per_cluster"]
    rng = np.random.default_rng(seed)
    n_c = max(cfg["min_clusters"], n // cfg["rows_per_cluster"])
    centers = (rng.standard_normal((n_c, d), dtype=np.float32)
               * np.float32(cfg["center_scale"]))
    cluster_of = rng.integers(0, n_c, size=n)
    x = _vectors(rng, centers, cluster_of, d)
    if cfg["normalize"]:
        x /= np.linalg.norm(x, axis=1, keepdims=True)
    cluster_kws = np.argsort(rng.random((n_c, vocab)), axis=1)[:, :per]
    words = np.zeros((n, vocab), bool)
    rows = np.arange(n)
    words[rows[:, None], cluster_kws[cluster_of]] = True
    extra = rng.random(n) < sch["keywords"]["extra_word_prob"]
    extra_word = rng.integers(0, vocab, size=n)
    words[rows[extra], extra_word[extra]] = True
    dates = rng.integers(0, sch["date"]["range"], size=n).astype(np.int32)
    names = np.asarray(KEYWORD_NAMES[:vocab], dtype=object)
    caption = np.asarray(["photo of " + " ".join(names[np.flatnonzero(w)])
                          for w in words], dtype=object)
    return Corpus(x=x, columns={"keywords": words, "date": dates,
                                "caption": caption},
                  kinds={"keywords": "keywords", "date": "int",
                         "caption": "text"},
                  cluster_of=cluster_of, centers=centers,
                  cluster_keywords=cluster_kws,
                  normalized=bool(cfg["normalize"]))


GENERATORS = {"lcps": lcps, "hcps": hcps}


def make_corpus(cfg: dict, seed: int) -> Corpus:
    """The configuration's corpus: drawn from the run's seed, or, where
    the configuration names a ``corpus_seed``, one fixed corpus for every
    run, as a deployment serves one data set."""
    return GENERATORS[cfg["generator"]](cfg, cfg.get("corpus_seed", seed))


# ---------------------------------------------------------------------------
# queries
# ---------------------------------------------------------------------------


def _balanced(rng, count: int, values: int) -> np.ndarray:
    """``count`` draws of 0..values-1, each equally often, shuffled."""
    return rng.permutation(np.arange(count) % values)


def _far_clusters(centers: np.ndarray) -> np.ndarray:
    sq = (centers * centers).sum(1)
    dist = sq[:, None] + sq[None, :] - 2.0 * centers @ centers.T
    return np.argmax(dist, axis=1)


class Traffic:
    """The query stream of one mix over one corpus, from one seed.

    Query ``i`` is a corpus row plus Gaussian noise of ``query_noise``
    times the rows' per-coordinate scale (re-normalised on a normalised
    corpus), with a predicate of the mix's family.  ``stream`` separates
    the warm-up's queries from the window's.  Where the mix names a
    ``query_seed``, block ``b`` is drawn from it, not from ``seed``, and
    ``seed`` permutes the block's queries: a closed loop whose clients
    fill one block a round then serves the same rounds from every seed."""

    def __init__(self, mix: dict, corpus: Corpus, seed: int, stream: int):
        self.mix = mix
        self.corpus = corpus
        self.seed = seed
        self.stream = stream
        self.scale = float(np.sqrt(np.mean(corpus.x[:4096] ** 2)))
        self._far = (None if corpus.cluster_keywords is None
                     else _far_clusters(corpus.centers))
        self._buf_x = np.zeros((0, corpus.d), np.float32)
        self._buf_p: List[dict] = []
        self._block = 0

    def take(self, count: int):
        """The next ``count`` queries: (xq (count, d) float32, preds)."""
        while len(self._buf_p) < count:
            xq, preds = self._draw_block(self._block)
            self._block += 1
            self._buf_x = np.concatenate([self._buf_x, xq])
            self._buf_p += preds
        xq, self._buf_x = self._buf_x[:count], self._buf_x[count:]
        preds, self._buf_p = self._buf_p[:count], self._buf_p[count:]
        return xq, preds

    def _draw_block(self, b: int):
        c = self.corpus
        fixed = self.mix.get("query_seed")
        rng = np.random.default_rng(
            [self.seed if fixed is None else fixed, self.stream, b])
        qi = rng.integers(0, c.n, size=BLOCK)
        xq = c.x[qi] + (np.float32(self.mix["query_noise"] * self.scale)
                        * rng.standard_normal((BLOCK, c.d), dtype=np.float32))
        if c.normalized:
            xq /= np.linalg.norm(xq, axis=1, keepdims=True)
        preds = self._predicates(self.mix["predicate"], rng, qi)
        if fixed is not None:
            order = np.random.default_rng(
                [self.seed, self.stream, b]).permutation(BLOCK)
            xq, preds = xq[order], [preds[i] for i in order]
        return xq.astype(np.float32), preds

    def _predicates(self, spec: dict, rng, qi) -> List[dict]:
        fam = spec["family"]
        col = spec.get("column")
        if fam == "equals":
            vals = _balanced(rng, BLOCK, spec["values"])
            return [{"op": "eq", "column": col, "value": int(v)}
                    for v in vals]
        if fam == "between":
            span = spec["values"] - spec["width"]
            los = _balanced(rng, BLOCK, span)
            return [{"op": "between", "column": col, "lo": int(lo),
                     "hi": int(lo) + spec["width"]} for lo in los]
        if fam == "contains_any":
            return [{"op": "contains_any", "column": col,
                     "words": [int(w) for w in ws]}
                    for ws in self._correlated_words(spec, rng, qi)]
        raise ValueError(f"unknown predicate family {fam!r}")

    def _correlated_words(self, spec: dict, rng, qi) -> List[np.ndarray]:
        """Words of the query's own cluster (pos), of the cluster farthest
        from it (neg) or of a random cluster (none), in the shares the
        mix gives; the first 1..max_words of that cluster's words."""
        c = self.corpus
        regimes = spec["correlation"]
        corr = _balanced(rng, BLOCK, len(regimes))
        nwords = _balanced(rng, BLOCK, spec["max_words"]) + 1
        rand_c = rng.integers(0, len(c.cluster_keywords), size=BLOCK)
        own = c.cluster_of[qi]
        target = {"pos": own, "neg": self._far[own], "none": rand_c}
        out = []
        for i in range(BLOCK):
            cl = target[regimes[corr[i]]][i]
            out.append(c.cluster_keywords[cl][:nwords[i]])
        return out
