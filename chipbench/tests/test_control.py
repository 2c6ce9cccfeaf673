"""The controls, put in the program's place, come out not correct.

Two controls (``reference.py``): the reference computed from vectors and
queries rounded to bfloat16, the precision below the configurations'
float32; and post-filtering, exact unfiltered top ceil(k/s) rows, then the
predicate.  For each cell both are read on the cell's own corpus and
queries, from the benchmark's generators:

    python3 chipbench/tests/test_control.py --seeds 11,12,13

reads them at the cells' real sizes, over as many queries as a run
compares (two rounds of the closed loop).  The test below reads them at a
small size.
"""
import argparse
import dataclasses
import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path[:0] = [str(Path(__file__).resolve().parents[2])]

from chipbench.generate import Traffic, make_corpus  # noqa: E402
from chipbench.harness import load_cell  # noqa: E402
from chipbench.reference import Reference, compare, correct  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
CONTROLS = ("bf16", "postfilter")


def bf16(a: np.ndarray) -> np.ndarray:
    """Round float32 to the nearest bfloat16 (ties to even), as float32."""
    u = np.asarray(a, np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1)))
    return (u & np.uint32(0xFFFF0000)).view(np.float32)


def readings(cell: str, seed: int, n: int, queries: int):
    """Each control's numbers on one seed, as ``compare`` makes them for
    a run of the program."""
    _, _, cfg, mix = load_cell(ROOT, cell)
    cfg["n"] = n
    k, limits = cfg["search"]["k"], cfg["correct_limits"]
    corpus = make_corpus(cfg, seed)
    xq, preds = Traffic(mix, corpus, seed, stream=0).take(queries)
    ref = Reference(corpus)
    low = Reference(dataclasses.replace(corpus, x=bf16(corpus.x)))
    low_q = bf16(xq)
    low_ids = low.answers(low_q, preds, k)
    post_ids = ref.answers(xq, preds, k, control=True)
    answers = {"bf16": (low_ids, low.distances(low_q, low_ids)),
               "postfilter": (post_ids, ref.distances(xq, post_ids))}
    out = {}
    for name in CONTROLS:
        ids, dists = answers[name]
        out[name], _ = compare(ref, xq, preds, ids, dists,
                               ["graph"] * len(preds), 0, k, limits)
    return out


@pytest.mark.parametrize("seed", [2**33 + 1, 7, 31337])
@pytest.mark.parametrize("cell", [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]])
def test_control_is_not_correct(cell, seed):
    for name, numbers in readings(cell, seed, n=4096, queries=256).items():
        assert not correct(numbers), (name, numbers)


def test_bf16_rounding():
    a = np.array([1.0, 1.00390625, 1.0078125, -3.14159265], np.float32)
    assert bf16(a).tolist() == [1.0, 1.0, 1.0078125, -3.140625]


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--n", type=int, default=131072)
    ap.add_argument("--cell", help="this cell only")
    args = ap.parse_args()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for cell in [w["name"] for w in bench["workloads"]
                 if args.cell in (None, w["name"])]:
        count = 2 * load_cell(ROOT, cell)[3]["clients"]
        for s in args.seeds.split(","):
            r = readings(cell, int(s), args.n, count)
            print(json.dumps({
                "cell": cell, "seed": int(s), "queries": count,
                **{name: {m: c["value"] for m, c in numbers.items()}
                   for name, numbers in r.items()}}), flush=True)
