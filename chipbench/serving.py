"""The system under test, driven through ``ServingRuntime.submit``.

This is the benchmark's only module that imports the program.  It builds
the deployment's engine from the configuration file, and runs a closed
loop of ``clients`` single-query clients: each round submits one request
per client and drains the runtime with ``pump`` (the runtime's synchronous
dispatch for closed-loop drivers); a client's next request follows its
reply.  A wrapper around the engine handed to the runtime times each
dispatch.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import numpy as np

from .generate import Corpus


def program_predicate(pred: dict):
    """The program's predicate tree for one of the benchmark's dicts."""
    from repro.core.predicates import Between, ContainsAny, Equals
    op = pred["op"]
    if op == "eq":
        return Equals(pred["column"], pred["value"])
    if op == "between":
        return Between(pred["column"], pred["lo"], pred["hi"])
    if op == "contains_any":
        return ContainsAny(pred["column"], tuple(pred["words"]))
    raise ValueError(f"unknown predicate op {op!r}")


def program_table(corpus: Corpus):
    """The corpus's columns as the program's ``AttributeTable``."""
    import jax.numpy as jnp
    from repro.core.predicates import AttributeTable, pack_multihot
    ints, bitsets, strs, vocab = {}, {}, {}, {}
    for name, kind in corpus.kinds.items():
        col = corpus.columns[name]
        if kind == "int":
            ints[name] = jnp.asarray(col)
        elif kind == "keywords":
            bitsets[name] = jnp.asarray(pack_multihot(
                [np.flatnonzero(row) for row in col], col.shape[1]))
            vocab[name] = int(col.shape[1])
        elif kind == "text":
            strs[name] = col
        else:
            raise ValueError(f"unknown column kind {kind!r}")
    return AttributeTable(int_cols=ints, bitset_cols=bitsets, str_cols=strs,
                          n_keywords=vocab)


def build_engine(cfg: dict, corpus: Corpus):
    """The deployment's serving engine over the corpus, built on the
    default device with the program's default execution policy."""
    import jax.numpy as jnp
    from repro.core import AcornConfig
    from repro.serve import EngineConfig, ServingEngine
    ix, eng = cfg["index"], cfg["engine"]
    acorn = AcornConfig(M=ix["M"], gamma=ix["gamma"], m_beta=ix["m_beta"],
                        ef_search=cfg["search"]["ef"],
                        variant=ix["variant"], metric=cfg["metric"])
    ecfg = EngineConfig(batch_size=eng["batch_size"], k=cfg["search"]["k"],
                        ef=cfg["search"]["ef"], n_shards=eng["n_shards"])
    return ServingEngine(jnp.asarray(corpus.x), program_table(corpus),
                         acorn, ecfg, seed=cfg["index_seed"])


@dataclass
class Dispatch:
    start: float
    end: float


class TimedEngine:
    """The engine as the runtime sees it, with each dispatch timed.

    ``fault``, for the benchmark's own tests only, rewrites each result
    before the runtime sees it."""

    def __init__(self, engine, fault: Optional[Callable] = None):
        self._engine = engine
        self._fault = fault
        self.dispatches: List[Dispatch] = []

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def search_batch(self, request, predicates=None):
        import jax
        from jax.profiler import TraceAnnotation
        t0 = time.perf_counter()
        with TraceAnnotation("chipbench.engine.search_batch"):
            res = self._engine.search_batch(request, predicates)
            jax.block_until_ready(res.ids)
        t1 = time.perf_counter()
        self.dispatches.append(Dispatch(t0, t1))
        return res if self._fault is None else self._fault(res)


@dataclass
class Request:
    """One single-query request of the window, as the client saw it."""

    sent: float
    done: Optional[float] = None
    shed: bool = False
    ids: Optional[np.ndarray] = None      # (k,) served ids, -1 padded
    dists: Optional[np.ndarray] = None    # (k,) served distances
    route: str = ""                       # the route that served it
    dist_comps: float = 0.0


@dataclass
class Window:
    """What the measured window produced, for the metric readers."""

    start: float
    requests: List[Request] = field(default_factory=list)
    xq: Optional[np.ndarray] = None     # (m, d) the requests' queries
    preds: List[dict] = field(default_factory=list)
    dispatches: List[Dispatch] = field(default_factory=list)
    engine_stats: dict = field(default_factory=dict)


def _request(xq_row, pred: dict, k: int):
    from repro.core import SearchRequest
    return SearchRequest(xq=xq_row[None], predicates=[program_predicate(pred)],
                         k=k)


def _finish(req: Request, ticket, done: float) -> None:
    if not ticket.done():
        return
    res = ticket.result(timeout=0)
    req.done = done
    req.shed = bool(np.asarray(res.shed).any())
    req.ids = np.asarray(res.ids)[0]
    req.dists = np.asarray(res.dists)[0]
    req.route = "" if res.routes is None else str(np.asarray(res.routes)[0])
    req.dist_comps = float(np.asarray(res.stats["dist_comps"]).sum())


def closed_round(rt, xq: np.ndarray, preds: List[dict],
                 k: int) -> List[Request]:
    """One round of a closed loop: one request per row, submitted
    together and drained by ``pump``."""
    batch = []
    for i in range(len(preds)):
        req = Request(sent=time.perf_counter())
        batch.append((req, rt.submit(_request(xq[i], preds[i], k))))
    rt.pump()
    done = time.perf_counter()
    for req, ticket in batch:
        _finish(req, ticket, done)
    return [r for r, _ in batch]


def closed_rounds(rt, traffic, clients: int, seconds: float, k: int,
                  window: Window) -> None:
    """Closed loop: round after round of ``clients`` requests, every
    round that starts before ``seconds`` have passed; a client's next
    request follows its reply."""
    t_end = window.start + seconds
    while time.perf_counter() < t_end:
        xq, preds = traffic.take(clients)
        window.requests += closed_round(rt, xq, preds, k)
        window.xq = xq if window.xq is None else np.concatenate(
            [window.xq, xq])
        window.preds += preds
