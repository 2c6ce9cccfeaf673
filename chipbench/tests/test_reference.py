"""The plain reference agrees with the program's exact masked top-k at a
small size, for every predicate family the mixes draw; and each number
the comparison makes counts what it says."""
import json

import numpy as np
import pytest

from chipbench.generate import Traffic, make_corpus
from chipbench.reference import Reference, compare, dist_err, recall
from conftest import ROOT

FAMILIES = [
    ("sift1m-lcps", {"family": "equals", "column": "label", "values": 12}),
    ("sift1m-lcps", {"family": "between", "column": "label", "values": 12,
                     "width": 2}),
    ("laion1m-hcps", {"family": "contains_any", "column": "keywords",
                      "max_words": 3, "correlation": ["pos", "neg", "none"]}),
]


def corpus_of(config: str, n: int, seed: int):
    cfg = json.loads((ROOT / "chipbench" / "configs"
                      / f"{config}.json").read_text())
    cfg["n"] = n
    return make_corpus(cfg, seed)


@pytest.mark.parametrize("config,family", FAMILIES,
                         ids=[f["family"] for _, f in FAMILIES])
def test_reference_matches_program_masked_topk(config, family):
    import jax.numpy as jnp
    from repro.core.bruteforce import masked_topk
    from repro.core.plan import compile_predicates
    from chipbench.serving import program_predicate, program_table
    corpus = corpus_of(config, 3000, seed=2**33 + 11)
    mix = {"query_noise": 0.1, "predicate": family}
    xq, preds = Traffic(mix, corpus, seed=5, stream=0).take(64)
    table = program_table(corpus)
    masks = compile_predicates([program_predicate(p) for p in preds],
                               table).evaluate(table)
    ref = Reference(corpus)
    want = np.stack([ref.mask(p) for p in preds])
    assert np.array_equal(np.asarray(masks), want)
    ids, _ = masked_topk(jnp.asarray(xq), jnp.asarray(corpus.x), masks, 10)
    truth = ref.answers(xq, preds, 10)
    assert np.array_equal(np.asarray(ids), truth)
    assert ref.bad_ids(truth, preds) == 0
    assert np.all(recall(np.asarray(ids), truth) == 1.0)


def test_bad_ids_counts_each_fault():
    corpus = corpus_of("sift1m-lcps", 600, seed=3)
    ref = Reference(corpus)
    label = corpus.columns["label"]
    pred = {"op": "eq", "column": "label", "value": 4}
    good = np.flatnonzero(label == 4)[:3]
    fails = np.flatnonzero(label != 4)[0]
    served = np.array([[good[0], good[0], fails, 600, good[1], -1]])
    # one repeat, one failing row, one out of range
    assert ref.bad_ids(served, [pred]) == 3
    assert ref.bad_ids(np.array([[good[0], good[1], -1]]), [pred]) == 0


def test_recall_counts_reference_rows_found():
    ref = np.array([[1, 2, 3, 4], [5, -1, -1, -1], [-1, -1, -1, -1]])
    served = np.array([[4, 9, 1, -1], [7, 8, 9, 10], [-1, -1, -1, -1]])
    assert recall(served, ref).tolist() == [0.5, 0.0, 1.0]


def test_dist_err_is_the_largest_relative_gap():
    exact = np.array([[4.0, 2.0, np.nan], [0.0, 1.0, 8.0]])
    served = np.array([[4.0, 2.002, np.inf], [0.0, 1.0, 7.992]])
    assert dist_err(served, exact) == pytest.approx(1e-3)
    assert dist_err(np.array([[1e-9]]), np.array([[0.0]])) == np.inf
    ref = Reference(corpus_of("sift1m-lcps", 600, seed=3))
    xq = ref.corpus.x[:2] + 0.5
    ids = np.array([[0, 5, -1], [7, 600, 1]])
    d = ref.distances(xq, ids)
    assert np.isnan(d[0, 2]) and np.isnan(d[1, 1])
    assert d[0, 0] == pytest.approx(0.25 * ref.corpus.d)


def test_compare_counts_short_answers_and_prefilter_mismatches():
    corpus = corpus_of("sift1m-lcps", 600, seed=3)
    ref = Reference(corpus)
    mix = {"query_noise": 0.1, "predicate": {
        "family": "equals", "column": "label", "values": 12}}
    xq, preds = Traffic(mix, corpus, seed=9, stream=0).take(4)
    truth = ref.answers(xq, preds, 10)
    served = truth.copy()
    served[1, 5:] = -1                      # a short answer
    passing = np.flatnonzero(corpus.columns["label"] == preds[2]["value"])
    served[2, 9] = np.setdiff1d(passing, truth[2])[0]   # a near miss
    limits = {"recall_miss": 1.0, "dist_err": 1e-4}
    routes = ["prefilter", "graph", "prefilter", "graph"]
    numbers, _ = compare(ref, xq, preds, served,
                         ref.distances(xq, served).astype(np.float32),
                         routes, 0, 10, limits)
    assert numbers["short_answers"]["value"] == 1
    assert numbers["prefilter_mismatch"]["value"] == 1
    assert numbers["bad_ids"]["value"] == 0
    assert numbers["dist_err"]["value"] < 1e-6
