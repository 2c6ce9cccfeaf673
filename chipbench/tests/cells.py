"""Every cell of BENCHMARK.json cut to a size the CPU runs in seconds."""
import json
import time

from conftest import ROOT


def cells():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return [w["name"] for w in bench["workloads"]]


def tiny(cell: str, root=ROOT) -> dict:
    """Overrides that shrink a cell: a 2048-row corpus and at most 16
    clients."""
    from chipbench.harness import load_cell
    _, _, _, mix = load_cell(root, cell)
    return {"config": {"n": 2048},
            "traffic": {"clients": min(mix["clients"], 16)}}


def run_tiny(cell: str, seed: int, trace: bool = False, fault=None,
             seconds: float = 2.0, root=ROOT) -> dict:
    """One run of ``cell`` as ``root``'s BENCHMARK.json defines it."""
    from chipbench import harness
    harness.configure(ROOT)
    return harness.run(root, cell, seed, seconds, trace, time.perf_counter(),
                       require_accelerator=False,
                       overrides=tiny(cell, root), fault=fault)
