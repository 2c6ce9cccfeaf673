"""One run of one cell: set-up, the measured window, the check, the result.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up generates the corpus from the seed, builds the deployment's engine,
and warms the shapes the cell's traffic uses.  The window then drives
``ServingRuntime.submit`` for ``--seconds``.  Afterwards the device's peak
memory is read, the program is stopped, and every answer of the window is
compared with the plain reference (``reference.py``).  The last line of
standard output is the result; the last lines of standard error are the
numbers compared, each with its limit.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, Optional

import numpy as np

from . import reference, tracing
from .generate import Traffic, make_corpus
from .records import Run
from .serving import (TimedEngine, Window, build_engine, closed_round,
                      closed_rounds, program_predicate)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def configure(root: Path) -> None:
    """Process settings that must precede JAX's import: the persistent
    compilation cache at a fixed path inside the checkout, and no TPU
    runtime logs written outside it.  The cache has a directory of its
    own and no size bound: JAX's size-bounded cache stops writing for
    good once its directory holds an entry without an access-time file,
    as one written by an unbounded cache does, and then every run
    compiles everything again.  One cell's programs take some tens of
    MB."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache"
                                                  / "chipbench")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ["TPU_LOG_DIR"] = "disabled"
    src = str(root / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def load_cell(root: Path, name: str):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = json.loads((root / config["file"]).read_text())
    mix = json.loads((root / "chipbench" / "traffic"
                      / f"{cell['traffic']}.json").read_text())
    if mix["loop"] != "closed":
        raise ValueError(f"traffic {cell['traffic']!r}: only closed loops "
                         "are driven")
    return bench, cell, cfg, mix


def _metrics_of(bench: dict, cell: str, trace: bool):
    """The metrics this cell reports in this kind of run."""
    if not trace:
        return [m for m in bench["end_to_end"]
                if cell in m.get("workloads", [cell])]
    reported = {m["name"] for m in _metrics_of(bench, cell, False)}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell])
            and m["moves"] in reported]


def read_metric(root: Path, name: str, run: Run) -> Optional[float]:
    path = root / "chipbench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    value = mod.read(run)
    return None if value is None else float(value)


class _CompileCount:
    """Backend compiles while ``on`` (JAX's monitoring events)."""

    def __init__(self):
        import jax
        self.on, self.count, self.seconds = False, 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, secs, **_):
        if self.on and name == COMPILE_EVENT:
            self.count += 1
            self.seconds += secs


def _to_prefilter(rt, preds) -> np.ndarray:
    """(m,) bool: where the engine's §5.2 router will send a predicate to
    the pre-filter route, by its own selectivity estimate (the runtime's
    public ``estimate_selectivity``) against the index's ``s_min``."""
    program = rt.engine.compile([program_predicate(p) for p in preds])
    return np.asarray(rt.estimate_selectivity(program)) < rt.engine.acorn.s_min


def _warm_up(rt, corpus, mix: dict, seed: int, seconds: float,
             k: int) -> None:
    """Compile and load what the window will run.  The program splits
    each dispatch between its two routes by predicate, and the shapes it
    runs follow the dispatch's size and that split, so the warm-up runs
    one round for each split among the rounds the window can run, with
    that round's predicates and queries of a stream of their own."""
    warm = Traffic(mix, corpus, seed, stream=1)
    clients = mix["clients"]
    ahead = Traffic(mix, corpus, seed, stream=0)   # the window's stream
    made, r, rounds, fastest = set(), 0, 1, None
    while r < rounds:
        preds = ahead.take(clients)[1]
        split = int(_to_prefilter(rt, preds).sum())
        if split not in made:
            t0 = time.perf_counter()
            closed_round(rt, warm.take(clients)[0], preds, k)
            took = time.perf_counter() - t0
            fastest = took if fastest is None else min(fastest, took)
            made.add(split)
            rounds = max(rounds, math.ceil(seconds / fastest) + 1)
        r += 1
    log(f"warm-up: {len(made)} closed rounds for the splits {sorted(made)}"
        f" among the window's first {rounds} rounds")


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, require_accelerator: bool = True,
        overrides: Optional[Dict[str, dict]] = None,
        fault: Optional[Callable] = None) -> dict:
    """One run of one cell; returns the result line's object.

    ``overrides`` (``{"config": {...}, "traffic": {...}}``) and ``fault``
    exist for the benchmark's own tests, which run cells at a small size
    on the CPU, and with the timed path broken."""
    bench, cell, cfg, mix = load_cell(root, workload)
    for part, changes in (overrides or {}).items():
        {"config": cfg, "traffic": mix}[part].update(changes)
    import jax
    from repro.serve import RuntimeConfig, ServingRuntime
    devices = jax.devices()
    dev = devices[0]
    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__} compile cache "
        f"{jax.config.jax_compilation_cache_dir} (max "
        f"{jax.config.jax_compilation_cache_max_size} bytes)")
    if require_accelerator and dev.platform == "cpu":
        raise NoChip("JAX finds no accelerator; this benchmark runs only "
                     "on the chip")
    if len(devices) < cell["chips"]:
        raise NoChip(f"the cell asks for {cell['chips']} chips, JAX finds "
                     f"{len(devices)}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = _CompileCount()
    k = cfg["search"]["k"]

    corpus = make_corpus(cfg, seed)
    log(f"corpus {cfg['name']}: n={corpus.n} d={corpus.d} made in "
        f"{time.perf_counter() - t_start:.1f} s since start")
    t0 = time.perf_counter()
    engine = build_engine(cfg, corpus)
    log(f"engine built in {time.perf_counter() - t0:.1f} s")
    timed = TimedEngine(engine, fault)
    rt = ServingRuntime(timed, RuntimeConfig())
    t0 = time.perf_counter()
    _warm_up(rt, corpus, mix, seed, seconds, k)
    log(f"warm-up {time.perf_counter() - t0:.1f} s")

    traffic = Traffic(mix, corpus, seed, stream=0)
    stats0 = dict(engine.stats)
    n_disp0 = len(timed.dispatches)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s")

    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    compiles.on = True
    window = Window(start=time.perf_counter())
    span = (jax.profiler.TraceAnnotation(tracing.WINDOW) if trace_dir
            else contextlib.nullcontext())
    with span:
        closed_rounds(rt, traffic, mix["clients"], seconds, k, window=window)
    compiles.on = False
    if trace_dir:
        jax.profiler.stop_trace()
    window.dispatches = timed.dispatches[n_disp0:]
    window.engine_stats = {key: engine.stats[key] - stats0.get(key, 0)
                           for key in engine.stats}
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    log(f"window: {len(window.requests)} requests, "
        f"{len(window.dispatches)} dispatches, {compiles.count} compiles "
        f"({compiles.seconds:.3f} s) inside it")
    log("dispatch seconds: " + " ".join(
        f"{d.end - d.start:.3f}" for d in window.dispatches))
    del rt, timed, engine
    gc.collect()

    summary = None
    if trace_dir:
        t0 = time.perf_counter()
        summary = _read_trace(trace_dir, dev.device_kind, require_accelerator)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace read in {time.perf_counter() - t0:.1f} s")

    t0 = time.perf_counter()
    answered = [i for i, r in enumerate(window.requests)
                if r.done is not None and not r.shed]
    unanswered = sum(r.done is None for r in window.requests)
    got = [window.requests[i] for i in answered]
    served = (np.stack([r.ids for r in got]) if got
              else np.zeros((0, k), np.int64))
    served_d = (np.stack([r.dists for r in got]) if got
                else np.zeros((0, k), np.float32))
    routes = [r.route for r in got]
    numbers, rec = reference.compare(
        reference.Reference(corpus), window.xq[answered],
        [window.preds[i] for i in answered], served, served_d, routes,
        unanswered, k, cfg["correct_limits"])
    log(f"reference compared {len(answered)} answers "
        f"({routes.count('prefilter')} pre-filter-routed) in "
        f"{time.perf_counter() - t0:.1f} s")

    record = Run(seconds=seconds, setup_s=setup_s, window=window,
                 recall=rec, trace=summary)
    metrics = {}
    for m in _metrics_of(bench, workload, trace):
        value = read_metric(root, m["name"], record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    failed = (sum(r.shed for r in window.requests) + unanswered)
    out = {"correct": reference.correct(numbers),
           "attempted": len(window.requests), "failed": int(failed),
           "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        out["breakdown"] = {"device_ops": summary["device_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    out["compiles_in_window"] = compiles.count
    out["checks"] = numbers
    return out


def _read_trace(trace_dir: str, kind: str, on_chip: bool) -> Optional[dict]:
    paths = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not paths:
        log("the profiler wrote no trace")
        return None
    log(f"trace {paths[-1].name}: {paths[-1].stat().st_size} bytes")
    if on_chip:
        log(f"peaks of one {kind}: {tracing.peaks(kind)}")
        return tracing.reduce_trace(str(paths[-1]))
    return tracing.reduce_trace(str(paths[-1]),
                                device_plane=tracing.HOST_PLANE,
                                ops_line=r"^tf_XLA")


def main(argv=None) -> int:
    t_start = time.perf_counter()
    import argparse
    ap = argparse.ArgumentParser(description="One run of one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = Path(__file__).resolve().parent.parent
    if not (root / "src" / "repro").is_dir():
        log(f"no program under {root / 'src'}: run from a checkout of the "
            "repository")
        return 2
    configure(root)
    try:
        out = run(root, args.workload, args.seed, args.seconds,
                  bool(args.trace), t_start)
    except NoChip as e:
        log(str(e))
        return 1
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0
