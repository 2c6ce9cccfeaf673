"""The loop-aware HLO cost model: validated against XLA's cost_analysis on
loop-free programs, and against analytic counts for loops/collectives."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_cost import analyze_hlo

X = jax.ShapeDtypeStruct((256, 256), jnp.float32)


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


def _xla_cost(c):
    """XLA's own cost estimate of a compiled program (a dict)."""
    return c.cost_analysis()


def test_matches_xla_on_loop_free():
    def f(x, w):
        return jnp.tanh(x @ w)

    c = _compile(f, X, X)
    mine = analyze_hlo(c.as_text())
    xla = _xla_cost(c)
    assert abs(mine.flops - xla["flops"]) / xla["flops"] < 0.05
    assert abs(mine.bytes - xla["bytes accessed"]) / \
        xla["bytes accessed"] < 0.25


def test_xla_counts_loop_body_once_we_dont():
    """Documents WHY this module exists."""
    def one(x, w):
        return x @ w

    def scanned(x, w):
        return jax.lax.scan(lambda c, _: (c @ w, None), x, None,
                            length=8)[0]

    c1, c8 = _compile(one, X, X), _compile(scanned, X, X)
    assert _xla_cost(c8)["flops"] == pytest.approx(
        _xla_cost(c1)["flops"])               # XLA: body counted once
    m1, m8 = analyze_hlo(c1.as_text()), analyze_hlo(c8.as_text())
    assert m8.flops / m1.flops == pytest.approx(8.0, rel=0.05)


def test_nested_loops_multiply():
    def nested(x, w):
        def outer(c, _):
            inner = jax.lax.scan(lambda d, _: (d @ w, None), c, None,
                                 length=4)[0]
            return inner, None
        return jax.lax.scan(outer, x, None, length=3)[0]

    base = analyze_hlo(_compile(lambda x, w: x @ w, X, X).as_text())
    got = analyze_hlo(_compile(nested, X, X).as_text())
    assert got.flops / base.flops == pytest.approx(12.0, rel=0.05)


def test_dot_flops_with_batch_dims():
    a = jax.ShapeDtypeStruct((4, 64, 32), jnp.float32)
    b = jax.ShapeDtypeStruct((4, 32, 16), jnp.float32)

    def f(a, b):
        return jnp.einsum("bij,bjk->bik", a, b)

    mine = analyze_hlo(_compile(f, a, b).as_text())
    want = 2 * 4 * 64 * 16 * 32
    assert mine.flops == pytest.approx(want, rel=0.05)


def test_gather_bytes_not_full_operand():
    table = jax.ShapeDtypeStruct((100000, 64), jnp.float32)
    ids = jax.ShapeDtypeStruct((8,), jnp.int32)

    def f(t, i):
        return t[i]

    mine = analyze_hlo(_compile(f, table, ids).as_text())
    # touched bytes ~ 2x output (8x64 rows), NOT the 25.6MB table
    assert mine.bytes < 1e5


def test_collectives_counted_with_loop_multiplier():
    import subprocess, sys, os
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.hlo_cost import analyze_hlo
mesh = jax.make_mesh((8,), ("d",))

def f(x):
    def body(c, _):
        s = jax.lax.psum(c, "d")
        return c + 0 * s, None
    return jax.lax.scan(body, x, None, length=5)[0]

from repro.compat import shard_map
g = shard_map(f, mesh=mesh, in_specs=P(None, "d"), out_specs=P(None, "d"),
              check_vma=False)
c = jax.jit(g).lower(jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
cost = analyze_hlo(c.as_text())
ar = cost.coll.get("all-reduce", {"count": 0})
assert ar["count"] == 5, f"expected 5 all-reduces, got {ar}"
print("COLL_OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=600,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert "COLL_OK" in r.stdout, r.stdout + r.stderr
