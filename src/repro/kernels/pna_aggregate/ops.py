"""jit'd public op for the fused PNA aggregator."""
from __future__ import annotations

import functools

import jax

from .kernel import pna_aggregate_pallas
from .ref import pna_aggregate_ref, pna_aggregate_segment_ref


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def pna_aggregate(adj, feats, use_kernel: bool = True,
                  interpret: bool = False):
    """Dense-batched PNA aggregation: (B,N,N), (B,N,F) -> (B,N,4F)."""
    if not use_kernel:
        return pna_aggregate_ref(adj, feats)
    return pna_aggregate_pallas(adj, feats, interpret=interpret)


pna_aggregate_segment = pna_aggregate_segment_ref
