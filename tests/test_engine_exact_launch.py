"""Engine launches with and without the trash tile.

A walk in which some grid step is invalid parks that step's write-back
on a trash tile appended to the state; a walk whose every step is valid
(``core.schedule.every_step_valid``) launches on the state itself.
Each case runs one step of a body through the jitted engine, in
interpret mode, and checks the predicate against the walk's table, the
output against the oracle, and that the non-donated input is intact.
"""

import functools

import jax
import numpy as np
import pytest

from repro.core.schedule import (
    SimplexSchedule,
    every_step_valid,
    registered_kinds,
    resolve_kind,
)
from repro.kernels import engine as E
from repro.kernels import ref as R

# (m, kind): every m = 2 kind, and an inexact and an exact walk at m = 3.
CASES = [(2, kind) for kind in registered_kinds(2)] + [(3, "hmap"), (3, "table")]
SIZE = {2: (16, 4), 3: (8, 2)}  # (n, rho): four tiles a side


@pytest.mark.parametrize("body", ["accum", "ca"])
@pytest.mark.parametrize("m, kind", CASES)
def test_launch_matches_oracle_and_keeps_its_input(m, kind, body):
    n, rho = SIZE[m]
    nb = n // rho
    sched = SimplexSchedule(m, nb, resolve_kind(m, nb, kind))
    assert every_step_valid(sched) == bool(sched.table()[:, -1].all())

    rng = np.random.default_rng(7 * m + len(kind))
    high = 2 if body == "ca" else 1 << 20
    x = rng.integers(0, high, size=(n,) * m, dtype=np.int32)
    if body == "ca":
        x = x * np.asarray(R.simplex_mask(m, n, np.int32))
        want = R.ca2d_step(x) if m == 2 else R.ca_md_step(x)
    else:
        want = R.accum_md(x)
    inside = np.asarray(R.simplex_mask(m, n)).astype(bool)
    want = np.where(inside, np.asarray(want), x)

    state = jax.numpy.asarray(x)
    launch = functools.partial(getattr(E, body), rho=rho, kind=kind)
    got = np.asarray(jax.jit(launch)(state))
    assert got.shape == x.shape
    assert np.array_equal(got, want)
    assert np.array_equal(np.asarray(state), x)
