"""Where JAX keeps its persistent compilation cache.

Call :func:`configure_compile_cache` from an entry point before its first
compile, never at import.  If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and nothing is set here.  Otherwise the cache goes to one
fixed directory inside the checkout (``<repo>/.jax_cache``, ignored by
git): the path is part of the cache key, so it must not move between runs.
``LIBTPU_INIT_ARGS`` and ``XLA_FLAGS`` are left as the environment set
them.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Place the compile cache and return the directory it uses."""
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
