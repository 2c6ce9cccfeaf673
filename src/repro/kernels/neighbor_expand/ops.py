"""Public op: fused 2-hop neighbor expansion with use_kernel routing.

``use_kernel=False`` (default) runs the sort-free jnp reference;
``use_kernel=True`` runs the Pallas kernel (``interpret=True`` for CPU
execution, compiled on TPU).  Both are bit-identical to the legacy
argsort-based expansion (``ref.neighbor_expand_argsort``).
"""
from __future__ import annotations

import jax.numpy as jnp

from .kernel import neighbor_expand_pallas
from .ref import neighbor_expand_ref

INVALID = -1


def neighbor_expand(row, nbr_table, pos, pass_mask=None, visited=None, *,
                    strategy: str, m: int, m_beta: int = 0,
                    use_kernel: bool = False, interpret: bool = False):
    """Up-to-m expansion ids per lane, in candidate order, -1 padded.

    row (B, cap) int32 1-hop neighbor ids (-1 padded); nbr_table (n_l, cap)
    the level's neighbor table; pos (n,) global id -> level row (or -1);
    pass_mask / visited (B, n) bool or None (None = all pass / none
    visited); the kernel path also takes them as ``pack_bitmap`` words.  strategy in {'filter', 'compress', 'two_hop'} (Figure 4);
    ``m_beta`` is the compressed head width (compress only).
    """
    if strategy not in ("filter", "compress", "two_hop"):
        raise ValueError(strategy)
    b = row.shape[0]
    if b == 0 or m <= 0:
        return jnp.full((b, max(m, 0)), INVALID, jnp.int32)
    fn = neighbor_expand_pallas if use_kernel else neighbor_expand_ref
    kw = dict(interpret=interpret) if use_kernel else {}
    return fn(row, nbr_table, pos, pass_mask, visited, strategy=strategy,
              m=m, m_beta=m_beta, **kw)
