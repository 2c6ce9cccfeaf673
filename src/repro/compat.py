"""JAX API shims.

``shard_map`` keeps one call signature across the repo: ``check_vma``
is passed only when the caller sets it.
"""
from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs, check_vma: bool | None = None):
    kw = {} if check_vma is None else {"check_vma": check_vma}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)
