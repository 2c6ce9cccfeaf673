"""Each cell runs end to end on the CPU at a tiny size and gives the
result line the contract asks for; the command refuses to run without
an accelerator and without the program."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from cells import cells, run_tiny
from conftest import ROOT


def _check_line(out: dict, root, cell: str, trace: bool) -> None:
    from chipbench.harness import _metrics_of, load_cell
    bench = load_cell(root, cell)[0]
    line = json.loads(json.dumps(out))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"])
    want = {m["name"] for m in _metrics_of(bench, cell, trace)}
    got = set(line["metrics"])
    if trace:
        assert got <= want and got, (got, want)
        assert line["device"]["busy_s"] > 0
        assert line["device"]["window_s"] > 0
        assert len(line["breakdown"]["device_ops"]) <= 10
        assert len(line["breakdown"]["idle_gaps"]) <= 10
    else:
        assert got == want
        for m in line["metrics"].values():
            assert m["value"] > 0


@pytest.mark.parametrize("trace", [False, True], ids=["window", "traced"])
@pytest.mark.parametrize("cell", cells())
def test_cell_runs_end_to_end(cell, trace):
    _check_line(run_tiny(cell, seed=2**33 + 101, trace=trace), ROOT, cell,
                trace)


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", cells()[0],
         "--seed", str(2**33 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=600)


def test_command_refuses_a_host_without_accelerator():
    res = _cli(ROOT)
    assert res.returncode != 0
    assert res.stdout.strip() == ""
    assert "no accelerator" in res.stderr


def test_command_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = _cli(tmp_path)
    assert res.returncode != 0
    assert res.stdout.strip() == ""
