"""Hypothesis property tests for the engine's new bodies (dev extra).

Invariants for the O(1)-effort body registrations the engine unlocked
(``edm3d`` / ``edm_md`` / ``ca_md``):

* **kind-swap consistency** — the schedule kind changes the grid walk,
  never the answer: integer bodies (CA) are bit-identical across every
  registered kind, float bodies (EDM) are bit-identical too because the
  per-tile compute depends only on the tile's coordinates, not the walk
  order (disjoint writes);
* **permutation consistency** — the EDM pair sum and the CA neighbour
  count are symmetric in the cell coordinates, so transposing the output
  by any axis permutation is a no-op on the (symmetric) m >= 3 domain;
* **split invariance** — element-local bodies launched per composite
  piece produce exactly the single-launch answer.

Gated behind the dev-extra skip in ``tests/conftest.py`` — deterministic
spot checks of the same invariants run unconditionally in
``tests/test_engine_parity.py``.

What keeps the module's time fixed and small:

* **one compile per shape** — every engine call goes through ``_launch``,
  a cache of jitted launches keyed by body, kind and keyword arguments,
  so the drawn seed changes the data a compiled launch runs on, never
  what gets compiled (the kind-swap and permutation tests share their
  ``table`` compiles);
* **derandomized examples** — every ``@settings`` has
  ``derandomize=True``, so each run draws the same examples;
* **m = 4 CA at n = 8 only** — each m = 4 CA shape compiles a kernel of
  3^4 halo tiles per kind, and n adds no code path;
  ``test_engine_parity.py::test_ca_parity`` checks every m = 4 kind at
  n = 6 and n = 8 against the oracle.
"""

import functools
import itertools

import numpy as np
import pytest

from conftest import require_dev_extra

require_dev_extra("hypothesis")
from hypothesis import given, settings
from hypothesis import strategies as st

import jax
import jax.numpy as jnp

from repro.kernels import engine as E
from repro.kernels import ref as R

_KINDS = {
    3: ["hmap", "octant", "bb", "table", "composite"],
    4: ["hmap", "bb", "table", "composite"],
}
_NS = {3: [4, 8, 12], 4: [4, 6, 8]}
_CA_NS = {3: _NS[3], 4: [8]}


@functools.lru_cache(maxsize=None)
def _launch(body, kind, **kw):
    """The jitted ``E.<body>(x, rho=2, kind=kind, **kw)``, built once."""
    return jax.jit(functools.partial(getattr(E, body), rho=2, kind=kind, **kw))


def _points(seed, n):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, 3), jnp.float32)


def _state(seed, m, n):
    u = jax.random.uniform(jax.random.PRNGKey(seed), (n,) * m)
    return ((u < 0.4).astype(jnp.int32)) * R.simplex_mask(m, n, jnp.int32)


@given(m=st.sampled_from([3, 4]), seed=st.integers(0, 2**16), data=st.data())
@settings(max_examples=10, deadline=None, derandomize=True)
def test_edm_md_kind_swap_consistent(m, seed, data):
    n = data.draw(st.sampled_from(_NS[m]))
    p = _points(seed, n)
    outs = [
        np.asarray(_launch("edm_md", kind, m=m)(p)) for kind in _KINDS[m]
    ]
    for kind, o in zip(_KINDS[m][1:], outs[1:]):
        assert np.array_equal(outs[0], o), kind


@pytest.mark.time_limit(600)
@given(m=st.sampled_from([3, 4]), seed=st.integers(0, 2**16), data=st.data())
@settings(max_examples=10, deadline=None, derandomize=True)
def test_ca_md_kind_swap_consistent(m, seed, data):
    n = data.draw(st.sampled_from(_CA_NS[m]))
    s = _state(seed, m, n)
    outs = [
        np.asarray(_launch("ca_md", kind)(s)) for kind in _KINDS[m]
    ]
    for kind, o in zip(_KINDS[m][1:], outs[1:]):
        assert np.array_equal(outs[0], o), kind


@given(m=st.sampled_from([3, 4]), seed=st.integers(0, 2**16), data=st.data())
@settings(max_examples=10, deadline=None, derandomize=True)
def test_edm_md_permutation_consistent(m, seed, data):
    n = data.draw(st.sampled_from(_NS[m]))
    perm = data.draw(
        st.sampled_from(list(itertools.permutations(range(m)))[1:])
    )
    p = _points(seed, n)
    out = np.asarray(_launch("edm_md", "table", m=m)(p))
    np.testing.assert_allclose(
        out, out.transpose(perm), rtol=1e-5, atol=1e-6
    )


@given(m=st.sampled_from([3, 4]), seed=st.integers(0, 2**16), data=st.data())
@settings(max_examples=10, deadline=None, derandomize=True)
def test_ca_md_permutation_consistent(m, seed, data):
    n = data.draw(st.sampled_from(_CA_NS[m]))
    perm = data.draw(
        st.sampled_from(list(itertools.permutations(range(m)))[1:])
    )
    s = np.asarray(_state(seed, m, n))
    # symmetric input -> symmetric output (integer CA: exact). The min
    # over every power of perm is invariant under perm; the min with
    # perm alone is not where perm is no involution (a 3-cycle).
    s_sym, power = s, perm
    while power != tuple(range(m)):
        s_sym = np.minimum(s_sym, s.transpose(power))
        power = tuple(power[i] for i in perm)
    assert np.array_equal(s_sym, s_sym.transpose(perm))
    out = np.asarray(_launch("ca_md", "table")(jnp.asarray(s_sym)))
    assert np.array_equal(out, out.transpose(perm))


@given(m=st.sampled_from([3, 4]), seed=st.integers(0, 2**16), data=st.data())
@settings(max_examples=10, deadline=None, derandomize=True)
def test_edm_md_split_invariant(m, seed, data):
    n = data.draw(st.sampled_from([6, 12]))
    p = _points(seed, n)
    a = np.asarray(_launch("edm_md", "composite", m=m, split=False)(p))
    b = np.asarray(_launch("edm_md", "composite", m=m, split=True)(p))
    assert np.array_equal(a, b)


@given(seed=st.integers(0, 2**16), data=st.data())
@settings(max_examples=10, deadline=None, derandomize=True)
def test_edm3d_matches_oracle(seed, data):
    n = data.draw(st.sampled_from(_NS[3]))
    p = _points(seed, n)
    got = np.asarray(_launch("edm3d", "table")(p))
    want = np.asarray(R.edm3d(p))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
