"""Hypothesis property tests for simplex sharding (dev extra, ISSUE 8).

Invariants of the fold partition and its sharded CA executor:

* **disjoint cover** — for any (S, k), the k shards' step ranges
  partition ``range(S)`` exactly, each shard is <= 2 contiguous
  ranges, and shard sizes differ by at most one (information-theoretic
  optimum);
* **skew bound** — ``shard_skew <= ceil(S/k)/(S/k) <= 1 + k/S`` for
  m in {2, 3, 4}, k in {2, 4, 8}, pow2 and non-pow2 n, and <= 1.05
  whenever S >= 20k (the acceptance regime);
* **bit-exact execution** — the sharded CA (per-shard engine launches
  + ownership-mask stitching) equals the single-device engine result
  bit-for-bit for random states, dimensions, and shard counts.

Gated behind the dev-extra skip in ``tests/conftest.py`` —
deterministic spot checks of the same invariants run unconditionally
in ``tests/test_simplex_sharding.py``.

The bit-exact property draws derandomized examples and runs each
sharded launch jitted, built once per (m, k): its seed changes the data
a compiled launch runs on, never what gets compiled, so the module's
time is small and the same on every run.
"""

import functools

import jax
import numpy as np

from conftest import require_dev_extra

require_dev_extra("hypothesis")
from hypothesis import given, settings
from hypothesis import strategies as st

import repro.kernels.ref as ref
from repro.core.schedule import SimplexSchedule, resolve_kind
from repro.distributed.simplex_sharding import (
    fold_partition,
    shard_schedules,
    shard_skew,
    sharded_ca,
)
from repro.kernels.ops import simplex_ca2d, simplex_ca_md

_NS = {2: [8, 12, 16, 20, 32], 3: [4, 6, 8, 12, 16], 4: [4, 6, 8]}


@functools.lru_cache(maxsize=None)
def _sharded_ca(k, kind):
    """The jitted ``sharded_ca(state, k, kind=kind)``, built once."""
    return jax.jit(functools.partial(sharded_ca, k=k, kind=kind))


@settings(max_examples=60, deadline=None)
@given(S=st.integers(1, 2000), k=st.integers(1, 16))
def test_fold_partition_properties(S, k):
    if k > S:
        return
    shards = fold_partition(S, k)
    cover = [i for s in shards for a, b in s.ranges for i in range(a, b)]
    assert sorted(cover) == list(range(S))
    sizes = [s.steps for s in shards]
    assert max(sizes) - min(sizes) <= 1
    assert all(1 <= len(s.ranges) <= 2 for s in shards)


@settings(max_examples=40, deadline=None)
@given(
    m=st.sampled_from([2, 3, 4]),
    ni=st.integers(0, 4),
    k=st.sampled_from([2, 4, 8]),
)
def test_skew_bound(m, ni, k):
    n = _NS[m][ni % len(_NS[m])]
    kind = resolve_kind(m, n, "hmap" if m == 2 else "table")
    sched = SimplexSchedule(m, n, kind)
    if k > sched.steps:
        return
    sk = shard_skew(sched, k)
    S = sched.steps
    assert sk <= np.ceil(S / k) / (S / k) + 1e-12
    assert sk <= 1 + k / S + 1e-12
    if S >= 20 * k:
        assert sk <= 1.05


@settings(max_examples=25, deadline=None)
@given(
    m=st.sampled_from([2, 3]),
    ni=st.integers(0, 3),
    k=st.sampled_from([2, 4]),
    seed=st.integers(0, 10_000),
)
def test_shard_cover_of_walk(m, ni, k, seed):
    ns = {2: [16, 24, 32], 3: [8, 12, 16]}[m]
    n = ns[ni % len(ns)]
    kind = resolve_kind(m, n, "hmap" if m == 2 else "table")
    base = SimplexSchedule(m, n, kind)
    subs = shard_schedules(base, k)
    tabs = np.concatenate([s.table() for s in subs])
    assert sorted(map(tuple, tabs.tolist())) == sorted(
        map(tuple, base.table().tolist())
    )


@settings(max_examples=10, deadline=None, derandomize=True)
@given(
    m=st.sampled_from([2, 3]),
    k=st.sampled_from([2, 4, 8]),
    seed=st.integers(0, 10_000),
)
def test_sharded_ca_bit_equals_engine(m, k, seed):
    n = 32 if m == 2 else 16
    rng = np.random.default_rng(seed)
    state = (rng.random((n,) * m) < 0.4).astype(np.int32)
    state = np.where(
        np.asarray(ref.simplex_mask(m, n)), state, 0
    ).astype(np.int32)
    kind = "hmap" if m == 2 else "table"
    if m == 2:
        want = np.asarray(simplex_ca2d(state, kind=kind))
    else:
        want = np.asarray(simplex_ca_md(state, kind=kind))
    got = np.asarray(_sharded_ca(k, kind)(state))
    assert np.array_equal(want, got)
