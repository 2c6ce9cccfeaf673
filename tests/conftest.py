import os

# Tests run on the single real CPU device (the 512-device override is
# strictly scoped to launch/dryrun.py per the multi-pod dry-run contract).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest

# Deterministic hypothesis profile: the invariant suites
# (test_search_invariants.py, test_merge_topk_properties.py) must not flake
# in CI, so generated examples are derandomized (fixed derivation from the
# test body) and the wall-clock deadline is off (CPU-JAX first-call jit
# costs would trip it).  Per-test @settings decorators still override
# max_examples; the profile supplies the defaults.  The import guard
# mirrors the suites themselves: without hypothesis installed they degrade
# to their always-on seeded sweeps.
try:  # pragma: no cover - exercised on minimal installs
    from hypothesis import settings

    settings.register_profile("repro-ci", derandomize=True, deadline=None)
    settings.load_profile("repro-ci")
except ImportError:
    pass


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def interpret_kernels():
    """Trace the test's Pallas kernels in TPU interpret mode.

    The program compiles its kernels by default, and the CPU has no Mosaic
    backend.  Tests that call a kernel wrapper pass ``interpret=True``
    themselves; this fixture is for code paths that expose no such knob
    (the model stack's ``pna_aggregate`` call)."""
    from jax.experimental.pallas import tpu as pltpu
    with pltpu.force_tpu_interpret_mode():
        yield
