"""Every seed draws the same work in another order: the same multiset of
predicate parameters, and with a mix's ``query_seed`` the same queries."""
from collections import Counter

import numpy as np

from chipbench.generate import BLOCK, Traffic
from test_reference import corpus_of


def _params(preds):
    return Counter(tuple(sorted((k, str(v)) for k, v in p.items()
                                if k != "words")) for p in preds)


def test_predicate_multisets_do_not_follow_the_seed():
    corpus = corpus_of("sift1m-lcps", 2048, seed=1)
    for family in ({"family": "equals", "column": "label", "values": 12},
                   {"family": "between", "column": "label", "values": 12,
                    "width": 2}):
        mix = {"query_noise": 0.1, "predicate": family}
        a = Traffic(mix, corpus, seed=1, stream=0).take(512)[1]
        b = Traffic(mix, corpus, seed=2**40 + 9, stream=0).take(512)[1]
        assert _params(a) == _params(b) and a != b


def test_word_counts_do_not_follow_the_seed():
    corpus = corpus_of("laion1m-hcps", 2048, seed=1)
    mix = {"query_noise": 0.1, "predicate": {
        "family": "contains_any", "column": "keywords", "max_words": 3,
        "correlation": ["pos", "neg", "none"]}}
    counts = [Counter(len(p["words"]) for p in
                      Traffic(mix, corpus, s, 0).take(768)[1])
              for s in (3, 4)]
    assert counts[0] == counts[1] == {1: 258, 2: 255, 3: 255}


def _rows(xq, preds):
    return sorted(zip((row.tobytes() for row in xq), map(str, preds)))


def test_query_seed_fixes_each_block_and_the_seed_orders_it():
    corpus = corpus_of("laion1m-hcps", 2048, seed=1)
    mix = {"query_noise": 0.1, "query_seed": 0, "predicate": {
        "family": "contains_any", "column": "keywords", "max_words": 3,
        "correlation": ["pos", "neg", "none"]}}
    a = Traffic(mix, corpus, 5, 0)
    b = Traffic(mix, corpus, 2**40 + 3, 0)
    for _ in range(2):
        (xa, pa), (xb, pb) = a.take(BLOCK), b.take(BLOCK)
        assert not np.array_equal(xa, xb)
        assert _rows(xa, pa) == _rows(xb, pb)


def test_queries_follow_the_seed_only():
    corpus = corpus_of("laion1m-hcps", 2048, seed=1)
    mix = {"query_noise": 0.1, "predicate": {
        "family": "contains_any", "column": "keywords", "max_words": 3,
        "correlation": ["pos", "neg", "none"]}}
    x1, p1 = Traffic(mix, corpus, 7, 0).take(300)
    x2, p2 = Traffic(mix, corpus, 7, 0).take(300)
    assert np.array_equal(x1, x2) and p1 == p2
    assert np.allclose(np.linalg.norm(x1, axis=1), 1.0, atol=1e-5)
