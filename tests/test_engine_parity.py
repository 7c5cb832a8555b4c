"""Differential parity harness: engine vs frozen legacy vs numpy oracle.

Three independent implementations of every workload are compared:

* the dimension-generic ``SimplexKernel`` engine (``kernels/engine.py``)
  — the implementation under test;
* the frozen hand-rolled kernels (``kernels/legacy.py``) — the original
  per-(body, dimension) ``pallas_call``s, kept verbatim precisely so
  this suite is not comparing the engine with itself;
* the pure-jnp oracles (``kernels/ref.py``) — the semantic ground truth.

Integer bodies (ACCUM, CA, MAP) must agree **bit for bit**; EDM at m=2
is also bit-exact against legacy (identical op order per pair), while
the m >= 3 EDM bodies (no legacy twin) are checked against the oracle to
float tolerance.  The sweep covers pow2 and non-pow2 n and every
schedule kind registered for the dimension; ``REPRO_PARITY_QUICK=1``
(the CI quick mode) trims it to one pow2 size and the analytic kinds.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import engine as E
from repro.kernels import legacy as L
from repro.kernels import ref as R

_QUICK = os.environ.get("REPRO_PARITY_QUICK", "").strip() in ("1", "true")

# (m, n, rho): pow2 and non-pow2 sides per dimension.
_SIZES = {
    2: [(16, 4), (24, 4)],
    3: [(8, 2), (12, 2)],
    4: [(8, 2), (6, 2)],
}
# Every kind the kernels accept per dimension ('composite' at m=2 is
# engine-only: the legacy 2D kernels launch a (w, h) grid).
_KINDS = {
    2: ["hmap", "rb", "bb", "composite"],
    3: ["hmap", "octant", "bb", "table", "composite"],
    4: ["hmap", "bb", "table", "composite"],
}
_LEGACY_2D_KINDS = ("hmap", "rb", "bb")

if _QUICK:
    _SIZES = {m: sizes[:1] for m, sizes in _SIZES.items()}
    _KINDS = {
        2: ["hmap", "bb"],
        3: ["hmap", "table"],
        4: ["hmap", "composite"],
    }


def _cases():
    return [
        (m, n, rho, kind)
        for m, sizes in _SIZES.items()
        for n, rho in sizes
        for kind in _KINDS[m]
    ]


def _ids(case):
    m, n, rho, kind = case
    return f"m{m}-n{n}-{kind}"


_CASES = _cases()


def _mask(m, n):
    return np.asarray(R.simplex_mask(m, n))


def _legacy_supports(m, kind):
    return m != 2 or kind in _LEGACY_2D_KINDS


# -- MAP --------------------------------------------------------------


@pytest.mark.parametrize("kind", _KINDS[2], ids=str)
@pytest.mark.parametrize("nb", [4] if _QUICK else [4, 6])
def test_map_parity_2d(nb, kind):
    from repro.core.schedule import resolve_kind

    got = np.asarray(E.map_table(nb, m=2, kind=kind))
    # both kernels apply the kernel-facing kind resolution (hmap -> rb
    # for non-pow2 m=2); the oracle table must be built the same way
    want = np.asarray(R.map_table_2d(nb, resolve_kind(2, nb, kind)))
    assert np.array_equal(got, want)
    if _legacy_supports(2, kind):
        assert np.array_equal(got, np.asarray(L.map2d(nb, kind)))


@pytest.mark.parametrize("m,nb", [(3, 4), (4, 2)])
def test_map_parity_md(m, nb):
    from repro.core.schedule import SimplexSchedule, resolve_kind

    for kind in _KINDS[m]:
        got = np.asarray(E.map_table(nb, m=m, kind=kind))
        sched = SimplexSchedule(m, nb, resolve_kind(m, nb, kind))
        want = np.asarray(sched.table())
        assert np.array_equal(got, want), kind


# -- ACCUM ------------------------------------------------------------


@pytest.mark.parametrize("case", _CASES, ids=_ids)
def test_accum_parity(case):
    m, n, rho, kind = case
    x = jnp.asarray(
        (np.arange(n**m, dtype=np.int32).reshape((n,) * m)) % 97
    )
    got = np.asarray(E.accum(x, rho=rho, kind=kind))
    msk = _mask(m, n)
    # oracle: +1 on the domain, input preserved off it
    want = np.asarray(R.accum_md(x))
    assert np.array_equal(got[msk == 1], want[msk == 1])
    assert np.array_equal(got[msk == 0], np.asarray(x)[msk == 0])
    # legacy: bit-equal everywhere (same trash-tile write discipline)
    if _legacy_supports(m, kind):
        legacy_fn = {2: L.accum2d, 3: L.accum3d}.get(m, L.accum_md)
        assert np.array_equal(
            got, np.asarray(legacy_fn(x, rho=rho, kind=kind))
        )


@pytest.mark.parametrize("m", [2, 3, 4])
def test_accum_split_invariance(m):
    n, rho = {2: (24, 4), 3: (12, 2), 4: (6, 2)}[m]
    x = jnp.asarray((np.arange(n**m, dtype=np.int32).reshape((n,) * m)) % 53)
    a = np.asarray(E.accum(x, rho=rho, kind="composite", split=False))
    b = np.asarray(E.accum(x, rho=rho, kind="composite", split=True))
    assert np.array_equal(a, b)


# -- EDM --------------------------------------------------------------


@pytest.mark.parametrize("case", _CASES, ids=_ids)
def test_edm_parity(case):
    m, n, rho, kind = case
    p = jax.random.normal(jax.random.PRNGKey(n + m), (n, 3), jnp.float32)
    if m == 2:
        got = np.asarray(E.edm2d(p, rho=rho, kind=kind))
    else:
        got = np.asarray(E.edm_md(p, m, rho=rho, kind=kind))
    msk = _mask(m, n)
    want = np.asarray(R.edm_md(p, m))
    if m == 2:
        # single pair, identical op order -> bit-exact vs the oracle
        assert np.array_equal(got, want)
        if _legacy_supports(2, kind):
            assert np.array_equal(
                got, np.asarray(L.edm2d(p, rho=rho, kind=kind))
            )
    else:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # off-domain cells hold the zeros seed exactly
    assert np.array_equal(got[msk == 0], np.zeros_like(got[msk == 0]))


def test_edm3d_is_edm_md_m3():
    p = jax.random.normal(jax.random.PRNGKey(0), (8, 3), jnp.float32)
    assert np.array_equal(
        np.asarray(E.edm3d(p, kind="table")),
        np.asarray(E.edm_md(p, 3, kind="table")),
    )


def test_edm_md_rejects_m2():
    p = jnp.zeros((8, 3), jnp.float32)
    with pytest.raises(ValueError):
        E.edm_md(p, 2)


# -- CA ---------------------------------------------------------------


@pytest.mark.parametrize("case", _CASES, ids=_ids)
def test_ca_parity(case):
    m, n, rho, kind = case
    key = jax.random.PRNGKey(n * m)
    s = (jax.random.uniform(key, (n,) * m) < 0.4).astype(jnp.int32)
    s = s * R.simplex_mask(m, n, jnp.int32)
    # jitted: eagerly, the m = 4 `table` launch (3^4 halo tiles) compiles
    # about three times slower
    got = np.asarray(jax.jit(functools.partial(E.ca, rho=rho, kind=kind))(s))
    msk = _mask(m, n)
    want = np.asarray(
        R.ca2d_step(s) if m == 2 else R.ca_md_step(s)
    )
    assert np.array_equal(got[msk == 1], want[msk == 1])
    assert np.array_equal(got[msk == 0], np.asarray(s)[msk == 0])
    if _legacy_supports(m, kind) and m in (2, 3):
        legacy_fn = {2: L.ca2d, 3: L.ca3d}[m]
        assert np.array_equal(
            got, np.asarray(legacy_fn(s, rho=rho, kind=kind))
        )


def test_ca_md_rejects_m2():
    with pytest.raises(ValueError):
        E.ca_md(jnp.zeros((8, 8), jnp.int32))


def test_ca_kind_swap_consistency():
    """Schedule kind changes the walk, never the answer (integers ->
    bit-exact).  The hypothesis sweep in test_property_engine.py widens
    this; the deterministic spot check always runs."""
    n = 8
    s = (jax.random.uniform(jax.random.PRNGKey(9), (n, n, n)) < 0.4).astype(
        jnp.int32
    )
    outs = [
        np.asarray(E.ca_md(s, kind=kind)) for kind in _KINDS[3]
    ]
    for o in outs[1:]:
        assert np.array_equal(outs[0], o)


# -- deprecation shims ------------------------------------------------


def test_legacy_wrappers_warn_and_delegate():
    """Every simplex_kernels entry point warns once and still answers."""
    from repro.kernels import simplex_kernels as K

    n = 8
    x2 = jnp.asarray(np.arange(n * n, dtype=np.int32).reshape(n, n))
    x3 = jnp.asarray(np.arange(n**3, dtype=np.int32).reshape(n, n, n))
    p = jax.random.normal(jax.random.PRNGKey(0), (n, 3), jnp.float32)
    s2 = (jax.random.uniform(jax.random.PRNGKey(1), (n, n)) < 0.4).astype(
        jnp.int32
    )
    s3 = (jax.random.uniform(jax.random.PRNGKey(2), (n, n, n)) < 0.4).astype(
        jnp.int32
    )
    calls = [
        (K.map2d, (4,), {}, lambda: E.map_table(4, m=2)),
        (K.accum2d, (x2,), {"rho": 4}, lambda: E.accum(x2, rho=4)),
        (K.edm2d, (p,), {"rho": 4}, lambda: E.edm2d(p, rho=4)),
        (K.ca2d, (s2,), {"rho": 4}, lambda: E.ca(s2, rho=4)),
        (K.accum3d, (x3,), {"rho": 2}, lambda: E.accum(x3, rho=2)),
        (K.ca3d, (s3,), {"rho": 2}, lambda: E.ca(s3, rho=2)),
        (K.accum_md, (x3,), {"rho": 2}, lambda: E.accum_md(x3, rho=2)),
    ]
    for fn, args, kwargs, engine_fn in calls:
        with pytest.warns(DeprecationWarning):
            got = fn(*args, **kwargs)
        assert np.array_equal(np.asarray(got), np.asarray(engine_fn())), (
            fn.__name__
        )


def test_grid_steps_shims_warn():
    from repro.kernels import simplex_kernels as K

    with pytest.warns(DeprecationWarning):
        assert K.grid_steps_2d(8, "hmap") == E.grid_steps(8, "hmap", m=2)
    with pytest.warns(DeprecationWarning):
        assert K.grid_steps_3d(8, "table") == E.grid_steps(8, "table", m=3)


def test_schedule2d_shim_warns():
    from repro.core.schedule import Schedule2D

    with pytest.warns(DeprecationWarning):
        Schedule2D(8, "hmap")


# -- engine surface ---------------------------------------------------


def test_registered_bodies():
    assert set(E.registered_bodies()) >= {"accum", "edm", "ca", "map"}


def test_engine_xla_executor_parity():
    n = 16
    x = jnp.asarray(np.arange(n * n, dtype=np.int32).reshape(n, n))
    a = np.asarray(E.accum(x, kind="hmap", executor="pallas"))
    b = np.asarray(E.accum(x, kind="hmap", executor="xla"))
    assert np.array_equal(a, b)
