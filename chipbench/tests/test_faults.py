"""A run whose timed path is broken underneath comes out not correct:
once for each fault a one-chip search cell can have."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from cells import cells, run_tiny


def altered(res):
    """Every answer's ids moved to the next row, where they are made."""
    ids = np.asarray(res.ids)
    moved = np.where(ids >= 0, (ids + 1) % 2048, ids)
    return dataclasses.replace(res, ids=jnp.asarray(moved))


def half_left_out(res):
    """The second half of the batch answered with nothing."""
    ids = np.array(res.ids)
    ids[len(ids) // 2:] = -1
    return dataclasses.replace(res, ids=jnp.asarray(ids))


def empty_lane(res):
    """The first request of each dispatch answered with no ids."""
    ids = np.array(res.ids)
    ids[0] = -1
    return dataclasses.replace(res, ids=jnp.asarray(ids))


class Stale:
    """Every dispatch answered with the first dispatch's results."""

    def __init__(self):
        self.first = None

    def __call__(self, res):
        if self.first is None:
            self.first = np.asarray(res.ids)
            return res
        rows = np.arange(res.ids.shape[0]) % len(self.first)
        return dataclasses.replace(res, ids=jnp.asarray(self.first[rows]))


FAULTS = {"altered": lambda: altered, "half_left_out": lambda: half_left_out,
          "empty_lane": lambda: empty_lane, "stale": Stale}


@pytest.mark.parametrize("fault", list(FAULTS))
@pytest.mark.parametrize("cell", cells())
def test_fault_is_not_correct(cell, fault):
    out = run_tiny(cell, seed=2**33 + 202, fault=FAULTS[fault]())
    assert out["correct"] is False
    failed = [n for n, c in out["checks"].items() if c["value"] > c["limit"]]
    assert failed, out["checks"]
