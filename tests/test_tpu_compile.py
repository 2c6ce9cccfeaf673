"""The search kernels compile for a TPU v5e (Mosaic, interpret=False).

Nothing runs: each test lowers and compiles one Pallas kernel for a
described v5e chip at the widths ``chip_smoke.py`` serves — a 2^19-row
shard of d = 128 vectors, ACORN-γ with M = 32, γ = 12, M_β = 64, and the
largest jit bucket of 256 lanes — so a layout Mosaic refuses fails here
instead of on the chip.  The topology is described inside a fixture: only
the worker that runs this file loads the TPU compiler.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.batched import DEFAULT_BUCKETS
from repro.kernels.gather_distance.kernel import gather_distance_pallas
from repro.kernels.neighbor_expand.kernel import (bitmap_words,
                                                  neighbor_expand_packed)

N = 1 << 19
D = 128
B = DEFAULT_BUCKETS[-1]
M, GAMMA, M_BETA = 32, 12, 64
R_SLACK = max(2, M // 2)                        # core/build.py reverse slack
CAP0 = min(M * GAMMA, M_BETA + 2 * M) + R_SLACK  # compressed level 0: 144
CAP_UPPER = M * GAMMA + R_SLACK                  # upper levels: 400
N_UPPER = N // M                                 # about 1/M of rows


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep it out of the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("m", [1, M, CAP_UPPER])
def test_gather_distance_compiles(one_chip, m):
    """m = 1 is the entry-point distance, m = M a hop's neighbors, and
    CAP_UPPER a full upper-level row of the predicate-agnostic descent."""
    def f(ids, q, x):
        return gather_distance_pallas(ids, q, x, interpret=False)

    compiled = jax.jit(f).lower(
        _shape(one_chip, (B, m), jnp.int32),
        _shape(one_chip, (B, D), jnp.float32),
        _shape(one_chip, (N, D), jnp.float32)).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("strategy,cap,n_rows,visited", [
    ("compress", CAP0, N, True),          # level-0 beam expansion
    ("filter", CAP_UPPER, N_UPPER, False),  # upper-level greedy descent
])
def test_neighbor_expand_compiles(one_chip, strategy, cap, n_rows, visited):
    """As the search calls it: the predicate packed once per batch, and at
    level 0 the packed visited bitmap updated in place."""
    def f(row, tbl, pos, pm, vis=None):
        return neighbor_expand_packed(row, tbl, pos, pm, vis,
                                      strategy=strategy, m=M, m_beta=M_BETA,
                                      interpret=False)

    words = (B, bitmap_words(N) // 128, 128)
    args = [_shape(one_chip, (B, cap), jnp.int32),
            _shape(one_chip, (n_rows, cap), jnp.int32),
            _shape(one_chip, (N,), jnp.int32),
            _shape(one_chip, words, jnp.int32)]
    if visited:
        args.append(_shape(one_chip, words, jnp.int32))
    compiled = jax.jit(f).lower(*args).compile()
    _assert_kernel(compiled)
