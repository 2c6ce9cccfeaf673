"""Pallas TPU kernel: masked L2/IP distance + exact top-k.

This is the hot spot of ACORN's pre-filtering fallback (§5.2), of
post-filter reranking, and of the two-tower ``retrieval_cand`` cell:
score a block of queries against the full corpus under a per-query boolean
mask and return the k best rows.

TPU mapping (DESIGN.md §2): distances ride the MXU as a (BQ, D) x (D, BC)
matmul per corpus tile; the predicate mask lives in VMEM alongside the
scores; each grid step extracts the tile-local top-k by iterative masked
argmax (k is small) into a per-tile output, and the thin jnp wrapper in
ops.py reduces the per-tile candidates exactly.  Exactness: global top-k is
contained in the union of tile-local top-k's.

Grid: (n_query_blocks, n_corpus_blocks); corpus is the minor axis so the
query tile and its norms stay resident in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _topk_block_kernel(q_ref, x_ref, mask_ref, scores_ref, ids_ref, *,
                       k: int, metric: str, bc: int):
    """One (query-tile, corpus-tile) cell.

    q_ref:    (bq, d)   query tile            (VMEM)
    x_ref:    (bc, d)   corpus tile           (VMEM)
    mask_ref: (bq, bc)  predicate mask tile   (VMEM)
    scores_ref: (bq, k) tile-local best scores (higher = better)
    ids_ref:    (bq, k) tile-local best row ids (corpus-tile-local)
    """
    j = pl.program_id(1)
    q = q_ref[...]
    x = x_ref[...]
    # scores on the MXU: -||q - x||^2 = 2 q.x - ||x||^2 - ||q||^2 ; the
    # ||q||^2 term is rank-preserving per query row, so it is dropped here
    # and reconstructed by the wrapper.
    qx = jax.lax.dot_general(q, x, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    if metric == "l2":
        xn = jnp.sum(x * x, axis=1)
        s = 2.0 * qx - xn[None, :]
    else:  # ip
        s = qx
    s = jnp.where(mask_ref[...], s, NEG_INF)

    # iterative top-k extraction (k static & small): k passes of masked max
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    def body(i, carry):
        s_cur, = carry
        m = jnp.max(s_cur, axis=1)                      # (bq,)
        amax = jnp.argmax(s_cur, axis=1)                # (bq,)
        scores_ref[:, i] = m
        ids_ref[:, i] = amax + j * bc
        s_cur = jnp.where(col == amax[:, None], NEG_INF, s_cur)
        return (s_cur,)

    jax.lax.fori_loop(0, k, body, (s,))


@functools.partial(jax.jit,
                   static_argnames=("k", "metric", "bq", "bc", "interpret"))
def filtered_topk_pallas(q, x, mask, k: int, metric: str = "l2",
                         bq: int = 128, bc: int = 512,
                         interpret: bool = False):
    """(B, d) x (n, d) with (B, n) mask -> per-tile candidates.

    Returns (scores, ids): (B, n_blocks * k) tile-local top-k, to be reduced
    by ops.filtered_topk.  Scores are 2 q.x - ||x||^2 for l2 (wrapper maps
    back to true squared distances) or q.x for ip.
    """
    b, d = q.shape
    n = x.shape[0]
    bq = min(bq, b)
    bc = min(bc, n)
    nqb = (b + bq - 1) // bq
    ncb = (n + bc - 1) // bc
    # pad to tile multiples; padded corpus rows are masked off
    qp = jnp.pad(q, ((0, nqb * bq - b), (0, 0)))
    xp = jnp.pad(x, ((0, ncb * bc - n), (0, 0)))
    mp = jnp.pad(mask, ((0, nqb * bq - b), (0, ncb * bc - n)))

    kern = functools.partial(_topk_block_kernel, k=k, metric=metric, bc=bc)
    scores, ids = pl.pallas_call(
        kern,
        grid=(nqb, ncb),
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bc, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bq, bc), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((bq, k), lambda i, j: (i, j)),
            pl.BlockSpec((bq, k), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nqb * bq, ncb * k), jnp.float32),
            jax.ShapeDtypeStruct((nqb * bq, ncb * k), jnp.int32),
        ],
        interpret=interpret,
    )(qp, xp, mp)
    return scores[:b], ids[:b]
