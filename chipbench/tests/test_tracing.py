"""The trace reduction on a small trace recorded here on the CPU, whose
XLA thunks stand in for the device's operations."""
import time

import pytest

from chipbench import tracing


def test_union_merges_overlaps():
    assert tracing._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_peaks_unknown_kind_is_an_error():
    assert tracing.peaks("TPU v5 lite")["hbm_byte_s"] == 819e9
    with pytest.raises(KeyError):
        tracing.peaks("TPU v99")


def test_reduce_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation
    f = jax.jit(lambda a: jnp.sin(a @ a).sum())
    a = jnp.ones((512, 512))
    f(a).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    t0 = time.perf_counter()
    with TraceAnnotation(tracing.WINDOW):
        for _ in range(3):
            f(a).block_until_ready()
            with TraceAnnotation("test.sleep"):
                time.sleep(0.05)
    wall = time.perf_counter() - t0
    jax.profiler.stop_trace()
    path = str(next(tmp_path.rglob("*.xplane.pb")))
    out = tracing.reduce_trace(path, device_plane=tracing.HOST_PLANE,
                               ops_line=r"^tf_XLA")
    assert 0.14 < out["window_s"] <= wall + 0.01
    assert 0 < out["busy_s"] < out["window_s"] - 0.14
    assert out["device_ops"] and out["device_ops"][0][1] > 0
    gaps = out["idle_gaps"]
    assert len([g for g in gaps if g[1] >= 0.045]) >= 2
    assert gaps[0][0] == "host: test.sleep"
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    assert tracing.reduce_trace(path) is None   # no TPU plane in it
