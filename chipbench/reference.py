"""The plain reference: exact filtered top-k, and the comparison that
decides ``correct``.

It shares no code with the program.  Each predicate is evaluated in numpy
from the raw attribute columns the benchmark generated (``generate.py``),
and distances are squared L2 in float64, which holds every float32
input exactly, so the top-k is exact up to true ties.

Two controls must come out not correct (``tests/test_control.py``): this
reference computed from vectors and queries rounded to bfloat16, the
precision below the configurations' float32, which ``dist_err`` catches;
and post-filtering, the exact unfiltered top ``ceil(k / s)`` rows, where
``s`` is the query's true selectivity, with the rows that fail the
predicate dropped (the strengthened post-filter baseline of ACORN §3.2),
which breaks the guarantee that an answer holds the k nearest passing
rows and which ``recall_miss`` catches.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence

import numpy as np

from .generate import Corpus


class Reference:
    """Exact answers over one corpus, computed on the host."""

    def __init__(self, corpus: Corpus, block: int = 128):
        self.corpus = corpus
        self.block = block
        self.x = corpus.x.astype(np.float64)
        self.sq = np.einsum("ij,ij->i", self.x, self.x)

    def mask(self, pred: dict) -> np.ndarray:
        """(n,) bool: the rows that pass ``pred``."""
        cols = self.corpus.columns
        op = pred["op"]
        if op == "eq":
            return cols[pred["column"]] == pred["value"]
        if op == "between":
            col = cols[pred["column"]]
            return (col >= pred["lo"]) & (col <= pred["hi"])
        if op == "contains_any":
            return cols[pred["column"]][:, list(pred["words"])].any(axis=1)
        raise ValueError(f"unknown predicate op {op!r}")

    def _distances(self, xq: np.ndarray) -> np.ndarray:
        q = np.asarray(xq, np.float64)
        d = q @ self.x.T
        d *= -2.0
        d += self.sq[None, :]
        d += np.einsum("ij,ij->i", q, q)[:, None]
        return d

    @staticmethod
    def _topk(d: np.ndarray, k: int) -> np.ndarray:
        """Row-wise ids of the k smallest finite entries, nearest first
        (ties by id); -1 where fewer than k are finite."""
        kk = min(k, d.shape[1])
        part = np.argpartition(d, kk - 1, axis=1)[:, :kk]
        dp = np.take_along_axis(d, part, axis=1)
        order = np.lexsort((part, dp), axis=1)
        ids = np.take_along_axis(part, order, axis=1)
        ids = np.where(np.isfinite(np.take_along_axis(dp, order, axis=1)),
                       ids, -1)
        if kk < k:
            ids = np.pad(ids, ((0, 0), (0, k - kk)), constant_values=-1)
        return ids

    def answers(self, xq: np.ndarray, preds: Sequence[dict], k: int,
                control: bool = False) -> np.ndarray:
        """(m, k) exact filtered top-k ids (-1 padded); with ``control``,
        the post-filter control's ids instead."""
        m = len(preds)
        out = np.full((m, k), -1, np.int64)
        for s in range(0, m, self.block):
            sl = slice(s, min(s + self.block, m))
            d = self._distances(xq[sl])
            masks = np.stack([self.mask(p) for p in preds[sl]])
            if control:
                out[sl] = self._postfilter(d, masks, k)
            else:
                d[~masks] = np.inf
                out[sl] = self._topk(d, k)
        return out

    def distances(self, xq: np.ndarray, ids: np.ndarray) -> np.ndarray:
        """(m, k) squared L2 from each query to each of its ids, summed in
        float64 over the coordinates' differences; NaN where an id is not a
        row."""
        ok = (ids >= 0) & (ids < self.corpus.n)
        rows = self.x[np.where(ok, ids, 0)]                   # (m, k, d)
        diff = rows - np.asarray(xq, np.float64)[:, None, :]
        return np.where(ok, np.einsum("mkd,mkd->mk", diff, diff), np.nan)

    def _postfilter(self, d: np.ndarray, masks: np.ndarray,
                    k: int) -> np.ndarray:
        out = np.full((len(d), k), -1, np.int64)
        for i, (row, mask) in enumerate(zip(d, masks)):
            s = mask.mean()
            if s == 0:
                continue
            want = min(len(row), math.ceil(k / s))
            cand = self._topk(row[None], want)[0]
            kept = cand[mask[cand]][:k]
            out[i, :len(kept)] = kept
        return out

    def bad_ids(self, served: np.ndarray, preds: Sequence[dict]) -> int:
        """Served ids that are out of range, repeated within an answer, or
        fail the answer's predicate."""
        n = self.corpus.n
        bad = 0
        for ids, pred in zip(served, preds):
            ids = ids[ids >= 0]
            inside = ids < n
            bad += int((~inside).sum()) + len(ids) - len(np.unique(ids))
            bad += int((~self.mask(pred)[ids[inside]]).sum())
        return bad


def recall(served: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """(m,) recall@k of each answer: the share of the reference's rows it
    holds (1 where no row passes the predicate)."""
    out = np.ones(len(ref))
    for i, (s, r) in enumerate(zip(served, ref)):
        r = r[r >= 0]
        if len(r):
            out[i] = np.isin(r, s[s >= 0]).sum() / len(r)
    return out


def dist_err(served_dists: np.ndarray, exact: np.ndarray) -> float:
    """The largest relative gap between a served distance and the exact
    distance of the same id (0 where both are 0; ids that are not rows
    are ``bad_ids``' to count)."""
    ok = ~np.isnan(exact)
    ds, dr = np.asarray(served_dists, np.float64)[ok], exact[ok]
    gap = np.abs(ds - dr)
    rel = np.divide(gap, dr, out=np.where(gap > 0, np.inf, 0.0),
                    where=dr > 0)
    return float(rel.max()) if len(rel) else 0.0


def compare(ref: Reference, xq: np.ndarray, preds: List[dict],
            served: np.ndarray, served_dists: np.ndarray,
            routes: Sequence[str], unanswered: int, k: int,
            limits: Dict[str, float]):
    """The numbers ``correct`` compares, each beside its limit, and the
    recall of each answer.  ``served`` (m, k) are the answers to the
    queries ``xq``/``preds``, ``served_dists`` their distances, ``routes``
    the route that served each; ``unanswered`` requests never came back.

    Exact numbers (limit 0): ``bad_ids``; ``unanswered``;
    ``short_answers``, answers with fewer ids than min(k, rows that pass),
    as a dropped lane gives; ``prefilter_mismatch``, pre-filter-routed
    answers whose ids are not the reference's, since that route is exact.
    From the configuration's ``correct_limits``, set from the readings
    PERF.md gives (sound runs below, a control above): ``recall_miss``
    (1 - mean recall@k) and ``dist_err`` (the largest relative gap
    between a served distance and its id's exact distance)."""
    truth = ref.answers(xq, preds, k)
    rec = recall(served, truth)
    passing = np.array([ref.mask(p).sum() for p in preds], np.int64)
    short = int(((served >= 0).sum(axis=1) < np.minimum(k, passing)).sum())
    pre = [i for i, r in enumerate(routes) if r == "prefilter"]
    mismatch = sum(set(served[i][served[i] >= 0].tolist())
                   != set(truth[i][truth[i] >= 0].tolist()) for i in pre)
    numbers = {
        "bad_ids": {"value": ref.bad_ids(served, preds), "limit": 0},
        "unanswered": {"value": int(unanswered), "limit": 0},
        "short_answers": {"value": short, "limit": 0},
        "prefilter_mismatch": {"value": int(mismatch), "limit": 0},
        "recall_miss": {"value": float(1.0 - rec.mean()) if len(rec)
                        else 0.0, "limit": limits["recall_miss"]},
        "dist_err": {"value": dist_err(served_dists,
                                       ref.distances(xq, served)),
                     "limit": limits["dist_err"]}}
    return numbers, rec


def correct(numbers: Dict[str, Dict[str, float]]) -> bool:
    return all(c["value"] <= c["limit"] for c in numbers.values())
