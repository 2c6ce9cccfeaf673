"""jit'd public op: batched neighbor gather + distance."""
from __future__ import annotations

import functools

import jax

from .kernel import gather_distance_pallas
from .ref import gather_distance_ref


@functools.partial(jax.jit, static_argnames=("metric", "use_kernel",
                                             "interpret"))
def gather_distance(ids, q, x, metric: str = "l2", use_kernel: bool = True,
                    interpret: bool = False):
    if not use_kernel:
        return gather_distance_ref(ids, q, x, metric)
    return gather_distance_pallas(ids, q, x, metric, interpret=interpret)
