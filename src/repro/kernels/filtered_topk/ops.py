"""jit'd public op for filtered (masked) top-k distance search."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import filtered_topk_pallas
from .ref import filtered_topk_ref


@functools.partial(jax.jit, static_argnames=("k", "metric", "use_kernel",
                                             "interpret"))
def filtered_topk(q, x, mask, k: int, metric: str = "l2",
                  use_kernel: bool = True, interpret: bool = False):
    """Exact masked top-k over the corpus.

    q (B, d), x (n, d), mask (B, n) -> (ids (B, k) int32 [-1 padded],
    dists (B, k): squared L2 or -IP).

    use_kernel routes through the Pallas tile kernel (interpret=True on CPU;
    compiled on TPU); the tile-local candidates are reduced exactly here.
    """
    if not use_kernel or k > 64:
        return filtered_topk_ref(q, x, mask, k, metric)
    scores, ids = filtered_topk_pallas(q, x, mask, k, metric,
                                       interpret=interpret)
    top_s, pos = jax.lax.top_k(scores, k)           # over n_blocks * k cands
    top_i = jnp.take_along_axis(ids, pos, axis=1)
    if metric == "l2":
        # kernel scores = 2 q.x - ||x||^2 ; true d2 = ||q||^2 - score
        qn = jnp.sum(q * q, axis=1, keepdims=True)
        dists = qn - top_s
    else:
        dists = top_s
    out_ids = jnp.where(jnp.isfinite(top_s), top_i, -1)
    dists = jnp.where(jnp.isfinite(top_s), dists, jnp.inf)
    return out_ids, dists
