"""Multi-device simplex sharding with skew control (DESIGN.md §7).

The paper's map H wins on one device by never launching the dead half
of the bounding box.  At cluster scale the same waste reappears as
*load skew*: naively slicing an m-simplex into equal-thickness slabs
along one axis gives the base slab up to m x the block volume of the
apex slab (the tetrahedral block-space imbalance of arXiv 1606.08881).
The fix is the same move the paper makes on-device, applied across
devices: partition the *schedule's step list* — the parallel space,
which enumerates exactly the live blocks — instead of the bounding
geometry.

``fold_partition`` generalizes ``core.schedule.folded_causal_pairs``
(query tile i paired with n-1-i) from m=2 to every dimension: the step
list is folded end-over-end (step 0, step S-1, step 1, step S-2, ...)
and dealt into k contiguous chunks of the folded order.  Each chunk
unfolds to at most TWO contiguous ranges of the original step order —
one near the apex, one near the base — so every shard keeps the seam
locality a halo exchange needs while its step count stays within one
block of ``S/k``.  ``shard_skew`` (max/mean shard block volume) is
therefore bounded by ``1 + k/S`` for the fold, versus ~m for the naive
slab split (``slab_skew`` quantifies the baseline).

``ShardSchedule`` exposes a shard as a first-class schedule — the same
``.grid`` / ``.steps`` / ``.map`` / ``.prefetch`` surface kernels
consume — so the ``SimplexKernel`` engine launches one shard exactly
like a full walk (``SimplexKernel(..., schedule=shard)``).  Seam halos
need no new machinery: the engine's 3^m-neighborhood subsystem already
fetches every neighbor tile of each scheduled block, so a seam face is
simply a neighbor fetch that lands on a tile *owned* by the adjacent
shard (DESIGN.md §7 seam-halo protocol).

Two executors drive a sharded CA step, both bit-exact against the
single-device engine:

* ``executor='engine'`` (default) — one per-shard ``SimplexKernel``
  launch, placed round-robin over the mesh devices; owned blocks are
  stitched with disjoint ownership masks.
* ``executor='spmd'`` — ``shard_map`` over a named mesh axis with the
  state held in a ``NamedSharding`` (axis-0 element slabs); seam planes
  travel by ``jax.lax.ppermute`` and each device steps its slab with
  true-coordinate domain masking.

Run ``examples/simplex_ca.py --devices k`` (under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` on a host) for
the end-to-end story: a long sharded CA that checkpoints through
``checkpoint/checkpointing.py`` and survives a simulated worker loss
via ``distributed.fault_tolerance.watchdog_restart``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import numpy as np

from repro.core.schedule import SimplexSchedule, every_step_valid, resolve_kind
from repro.core.simplex import simplex_volume

__all__ = [
    "StepShard",
    "ShardSchedule",
    "fold_partition",
    "shard_schedules",
    "shard_skew",
    "slab_skew",
    "shard_mesh",
    "shard_state",
    "ShardedSimplexCA",
    "sharded_ca",
]


# ---------------------------------------------------------------------------
# partition construction
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class StepShard:
    """One shard of a folded step-list partition.

    Attributes:
        index: Shard number in ``[0, k)``.
        k: Total shard count of the partition.
        ranges: Up to two ``(start, stop)`` half-open ranges of the base
            schedule's step order — the apex-side and base-side runs the
            fold pairs together (merged when they touch).
    """

    index: int
    k: int
    ranges: Tuple[Tuple[int, int], ...]

    @property
    def steps(self) -> int:
        """Total steps (block volume) this shard owns."""
        return sum(b - a for a, b in self.ranges)


def fold_partition(n_steps: int, k: int) -> Tuple[StepShard, ...]:
    """Fold a step list end-over-end into k balanced shards.

    The folded order visits steps ``0, S-1, 1, S-2, ...`` — the
    dimension-generic form of the ``folded_causal_pairs`` pairing
    ``(i, n-1-i)`` — and is dealt into k contiguous chunks whose sizes
    differ by at most one.  A contiguous chunk of the folded order
    unfolds to one range near each end of the original order, so every
    shard is at most two contiguous step ranges: skew stays within
    ``1 + k/S`` of perfect while seam count stays O(1) per shard.

    Args:
        n_steps: Length S of the step list to partition.
        k: Shard count, ``1 <= k <= n_steps``.

    Returns:
        Tuple of k ``StepShard``; together a disjoint cover of
        ``range(n_steps)``.

    Example:
        >>> [s.ranges for s in fold_partition(6, 3)]
        [((0, 1), (5, 6)), ((1, 2), (4, 5)), ((2, 4),)]
        >>> from repro.core.schedule import folded_causal_pairs
        >>> folded_causal_pairs(4).tolist()   # the m=2 special case ...
        [[0, 3], [1, 2]]
        >>> [s.ranges for s in fold_partition(4, 2)]  # ... is k = S/2
        [((0, 1), (3, 4)), ((1, 3),)]
    """
    if k < 1 or k > n_steps:
        raise ValueError(
            f"need 1 <= k <= n_steps, got k={k}, n_steps={n_steps}"
        )
    base, rem = divmod(n_steps, k)
    shards = []
    p0 = 0
    for s in range(k):
        p1 = p0 + base + (1 if s < rem else 0)
        front = ((p0 + 1) // 2, (p1 + 1) // 2)
        back = (n_steps - p1 // 2, n_steps - p0 // 2)
        ranges = tuple(
            (a, b) for a, b in (front, back) if b > a
        )
        if len(ranges) == 2 and ranges[0][1] == ranges[1][0]:
            ranges = ((ranges[0][0], ranges[1][1]),)
        shards.append(StepShard(index=s, k=k, ranges=ranges))
        p0 = p1
    return tuple(shards)


def shard_skew(schedule: SimplexSchedule, k: int) -> float:
    """Max/mean shard block volume of the folded k-way partition.

    The fold deals steps one at a time, so shard sizes differ by at
    most one block and the skew is bounded by ``1 + k/steps`` — below
    1.05 for every realistic launch (``steps >= 20k``), versus the ~m x
    imbalance of the naive slab split (``slab_skew``).

    Args:
        schedule: Any ``SimplexSchedule`` (O(1): only ``.steps`` is
            read — no table build).
        k: Shard count.

    Returns:
        ``max(shard steps) / mean(shard steps)`` over the k shards.

    Example:
        >>> from repro.core.schedule import SimplexSchedule
        >>> shard_skew(SimplexSchedule(3, 8, "table"), 4)  # 120 = 4*30
        1.0
        >>> round(shard_skew(SimplexSchedule(2, 100, "composite"), 8), 4)
        1.0012
    """
    sizes = [s.steps for s in fold_partition(schedule.steps, k)]
    return max(sizes) / (sum(sizes) / len(sizes))


def slab_skew(m: int, nb: int, k: int) -> float:
    """Block-volume skew of the naive equal-thickness axis-0 slab split.

    Layer ``l`` of the blocked m-simplex holds ``l+1`` blocks at m=2
    (row l of the inclusive lower triangle) and ``V^{m-1}(nb - l)``
    blocks at m >= 3; slicing the nb layers into k equal-thickness
    slabs therefore loads the base slab up to m x the mean — the
    imbalance the fold partition removes.

    Args:
        m: Simplex dimension.
        nb: Tile (block) count per side.
        k: Slab count, ``1 <= k <= nb``.

    Returns:
        ``max(slab volume) / mean(slab volume)`` over the k slabs.

    Example:
        >>> round(slab_skew(3, 8, 4), 3)   # base slab 64 vs mean 30
        2.133
        >>> round(slab_skew(2, 64, 8), 3)  # ~2x at m=2, as the paper's fold predicts
        1.862
    """
    if k < 1 or k > nb:
        raise ValueError(f"need 1 <= k <= nb, got k={k}, nb={nb}")
    if m == 2:
        vols = [lo + 1 for lo in range(nb)]
    else:
        vols = [simplex_volume(nb - lo, m - 1) for lo in range(nb)]
    base, rem = divmod(nb, k)
    sums, lo = [], 0
    for s in range(k):
        hi = lo + base + (1 if s < rem else 0)
        sums.append(sum(vols[lo:hi]))
        lo = hi
    return max(sums) / (sum(sums) / len(sums))


# ---------------------------------------------------------------------------
# shard schedules: the engine-facing surface
# ---------------------------------------------------------------------------


def _is_jax(x) -> bool:
    return type(x).__module__.startswith("jax")


class ShardSchedule:
    """A shard of a base schedule, exposed as a launchable schedule.

    Wraps a ``SimplexSchedule`` restricted to one ``StepShard``: the
    same ``.grid`` / ``.steps`` / ``.map`` / ``.prefetch`` surface the
    ``SimplexKernel`` engine consumes, so
    ``SimplexKernel(body, m, schedule=shard)`` launches exactly the
    shard's blocks.  The map decodes a shard-local linear index into
    the base step order (piecewise over the <= 2 ranges), then into the
    base grid's coordinates — pure index arithmetic, dual-backend.

    Example:
        >>> from repro.core.schedule import SimplexSchedule
        >>> base = SimplexSchedule(3, 4, "table")
        >>> shards = shard_schedules(base, 4)
        >>> [s.steps for s in shards]
        [5, 5, 5, 5]
        >>> import numpy as np
        >>> tabs = np.concatenate([s.table() for s in shards])
        >>> sorted(map(tuple, tabs)) == sorted(map(tuple, base.table()))
        True
    """

    kind = "shard"

    def __init__(self, base: SimplexSchedule, shard: StepShard):
        if shard.steps < 1:
            raise ValueError(f"empty shard {shard.index} of {shard.k}")
        self.base = base
        self.shard = shard
        self.m = base.m
        self.n = base.n
        self.grid = (shard.steps,)
        self.steps = shard.steps
        self.useful = shard.steps
        self.ranges = shard.ranges

    @property
    def prefetch(self):
        """The base schedule's scalar-prefetch payload (table kinds)."""
        return self.base.prefetch

    @property
    def exact(self) -> bool:
        """No grid step is invalid: a shard of an exact base walk."""
        return every_step_valid(self.base)

    def _global(self, lin):
        """Shard-local linear index -> base step-order index."""
        (a0, b0) = self.ranges[0]
        if len(self.ranges) == 1:
            return a0 + lin
        (a1, _) = self.ranges[1]
        l0 = b0 - a0
        if _is_jax(lin):
            import jax.numpy as jnp

            return jnp.where(lin < l0, a0 + lin, a1 + (lin - l0))
        return np.where(lin < l0, a0 + lin, a1 + (lin - l0))

    def map(self, lin, *prefetch):
        """Shard-local index -> ``(*coords, valid)`` of the base walk.

        Args:
            lin: Linear index/array in ``[0, self.steps)``.
            *prefetch: The prefetched table ref for table-driven bases.

        Returns:
            The base schedule's ``(*coords, valid)`` at the mapped step.
        """
        g = self._global(lin)
        ws, rem = [], g
        for gdim in self.base.grid:
            ws.append(rem % gdim)
            rem = rem // gdim
        return self.base.map(*ws, *prefetch)

    def table(self) -> np.ndarray:
        """Host-side ``(steps, m+1)`` walk table of this shard only."""
        lin = np.arange(self.steps, dtype=np.int64)
        if self.prefetch is not None:
            out = self.map(lin, self.prefetch)
        else:
            out = self.map(lin)
        cols = [np.asarray(c) for c in out[:-1]]
        cols.append(np.asarray(out[-1]).astype(np.int64))
        return np.stack(cols, axis=1).astype(np.int32)

    def owned_block_mask(self) -> np.ndarray:
        """Boolean ``(nb,)*m`` mask of blocks this shard owns.

        Valid steps only (array-axis order) — the stitching mask of the
        per-shard engine executor.  Host-side, O(shard steps).
        """
        tab = self.table()
        ok = tab[:, -1] != 0
        coords = tab[ok, : self.m]
        mask = np.zeros((self.n,) * self.m, dtype=bool)
        # table columns are math-order coords; array axis 0 is the last
        mask[tuple(coords[:, self.m - 1 - j] for j in range(self.m))] = True
        return mask

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (
            f"ShardSchedule({self.shard.index}/{self.shard.k}, "
            f"m={self.m}, n={self.n}, ranges={self.ranges}, "
            f"base={self.base.kind!r})"
        )


def shard_schedules(base: SimplexSchedule, k: int) -> Tuple[ShardSchedule, ...]:
    """Fold a schedule into k engine-launchable shard schedules.

    Args:
        base: The schedule to partition (any registered kind).
        k: Shard count, ``1 <= k <= base.steps``.

    Returns:
        k ``ShardSchedule`` whose step sets disjointly cover the base
        walk (fold partition: <= 2 contiguous ranges per shard).

    Example:
        >>> from repro.core.schedule import SimplexSchedule
        >>> subs = shard_schedules(SimplexSchedule(2, 16, "hmap"), 8)
        >>> sum(s.steps for s in subs), max(s.steps for s in subs)
        (136, 17)
    """
    return tuple(
        ShardSchedule(base, s) for s in fold_partition(base.steps, k)
    )


# ---------------------------------------------------------------------------
# mesh / layout helpers
# ---------------------------------------------------------------------------


def shard_mesh(k: int, axis: str = "shard"):
    """A 1-D device mesh of size k over the first k local devices.

    Args:
        k: Device count (<= ``jax.device_count()``; emulate on a host
            with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
        axis: Mesh axis name.

    Returns:
        ``jax.sharding.Mesh`` with one named axis of size k.
    """
    import jax
    from jax.sharding import Mesh

    devs = jax.devices()
    if len(devs) < k:
        raise ValueError(
            f"need {k} devices, found {len(devs)}; emulate with "
            "XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{k} (set before the first jax import)"
        )
    return Mesh(np.asarray(devs[:k]), (axis,))


def shard_state(state, mesh, axis: str = "shard"):
    """Place a domain array in the axis-0 slab ``NamedSharding`` layout.

    Args:
        state: ``(n,)*m`` domain array, ``n`` divisible by the mesh
            axis size.
        mesh: Mesh from ``shard_mesh``.
        axis: Mesh axis name to shard axis 0 over.

    Returns:
        ``state`` committed to ``NamedSharding(mesh, P(axis, None...))``.
    """
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    k = mesh.shape[axis]
    if state.shape[0] % k != 0:
        raise ValueError(
            f"axis 0 ({state.shape[0]}) must divide over {k} devices"
        )
    spec = P(axis, *([None] * (state.ndim - 1)))
    return jax.device_put(state, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# sharded CA execution
# ---------------------------------------------------------------------------


class ShardedSimplexCA:
    """k-way sharded CA stepping, bit-exact vs the single-device engine.

    ``executor='engine'``: each shard is one ``SimplexKernel('ca', ...)``
    launch over its ``ShardSchedule``, placed round-robin on the mesh
    devices; every shard reads the same input generation (the engine's
    3^m-neighborhood subsystem serves seam halos from neighbor-shard
    tiles present in its input), and the output generation is stitched
    from the disjoint per-shard ownership masks — so the composition is
    bit-identical to one fused launch.

    ``executor='spmd'``: one ``shard_map`` program over the mesh axis
    with the state in the axis-0 slab ``NamedSharding``; seam planes
    travel by ``ppermute`` and each device steps its slab under
    true-coordinate domain masking (free boundaries at m >= 3, periodic
    wrap at m=2 — the engine's per-dimension CA conventions).

    Args:
        m: Simplex dimension (>= 2).
        n: Domain side length in elements.
        k: Shard count.
        rho: Tile side for the engine executor (default
            ``engine.default_rho(m)``).
        kind: Base schedule kind (resolved via ``resolve_kind``).
        mesh: Optional mesh from ``shard_mesh``; None runs all shards
            on the default device (partition semantics unchanged).
        interpret: Pallas mode, None = per-backend policy.
    """

    def __init__(self, m: int, n: int, k: int, *, rho: Optional[int] = None,
                 kind: str = "hmap", mesh=None, interpret=None,
                 axis: str = "shard"):
        from repro.kernels.engine import SimplexKernel, default_rho

        self.m, self.n, self.k = m, n, k
        self.rho = default_rho(m, interpret) if rho is None else rho
        if n % self.rho != 0:
            raise ValueError(f"rho={self.rho} must divide n={n}")
        self.nb = n // self.rho
        self.kind = resolve_kind(m, self.nb, kind)
        self.mesh = mesh
        self.axis = axis
        self.interpret = interpret
        base = SimplexSchedule(m, self.nb, self.kind)
        self.base = base
        self.shards = shard_schedules(base, k)
        self._kernels = [
            SimplexKernel("ca", m, rho=self.rho, kind=self.kind,
                          interpret=interpret, schedule=sh)
            for sh in self.shards
        ]
        self._devices = None
        if mesh is not None:
            self._devices = list(mesh.devices.flat)
        self._masks = None  # element ownership masks, built lazily

    # -- engine executor ---------------------------------------------------

    def _ownership_masks(self):
        import jax.numpy as jnp

        if self._masks is None:
            reps = (self.rho,) * self.m
            masks = []
            for sh in self.shards:
                blk = sh.owned_block_mask()
                for ax, r in enumerate(reps):
                    blk = np.repeat(blk, r, axis=ax)
                masks.append(jnp.asarray(blk))
            self._masks = masks
        return self._masks

    def shard_outputs(self, state):
        """Each shard's engine launch on ``state``, one array per shard.

        With a mesh, shard i runs on mesh device i modulo the mesh
        size, and its output stays there.
        """
        import jax

        outs = []
        for i, kern in enumerate(self._kernels):
            x = state
            if self._devices is not None:
                x = jax.device_put(
                    state, self._devices[i % len(self._devices)]
                )
            outs.append(kern(x))
        return outs

    def step_engine(self, state):
        """One CA generation via per-shard engine launches + stitching."""
        import jax
        import jax.numpy as jnp

        masks = self._ownership_masks()
        outs = self.shard_outputs(state)
        out = state
        for y, mask in zip(outs, masks):
            if self._devices is not None:
                y = jax.device_get(y)
            out = jnp.where(mask, jnp.asarray(y), out)
        return out

    # -- SPMD executor -----------------------------------------------------

    def step_spmd(self, state):
        """One CA generation via shard_map + ppermute seam exchange.

        ``state`` may be host-resident or already committed to the slab
        ``NamedSharding``; the output keeps the sharded layout.
        """
        import jax

        if self.mesh is None:
            raise ValueError("executor='spmd' needs a mesh (shard_mesh(k))")
        if self.n % self.k != 0:
            raise ValueError(
                f"spmd executor slabs elements: n={self.n} must divide "
                f"over k={self.k}"
            )
        fn = _spmd_step_fn(self.m, self.n, self.k, self.mesh, self.axis)
        return fn(shard_state(jax.numpy.asarray(state), self.mesh, self.axis))

    def step(self, state, executor: str = "engine"):
        """One CA generation with the chosen executor."""
        if executor == "engine":
            return self.step_engine(state)
        if executor == "spmd":
            return self.step_spmd(state)
        raise ValueError(f"unknown executor {executor!r}")

    def run(self, state, steps: int, executor: str = "engine"):
        """``steps`` generations from ``state``; returns the final one."""
        for _ in range(steps):
            state = self.step(state, executor=executor)
        return state


_SPMD_CACHE = {}


def _spmd_step_fn(m: int, n: int, k: int, mesh, axis: str):
    """Build (and cache) the jitted shard_map CA step for (m, n, k)."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    key = (m, n, k, axis, tuple(d.id for d in mesh.devices.flat))
    if key in _SPMD_CACHE:
        return _SPMD_CACHE[key]

    slab = n // k
    spec = P(axis, *([None] * (m - 1)))
    periodic = m == 2
    fwd = [(i, (i + 1) % k) for i in range(k)]
    bwd = [(i, (i - 1) % k) for i in range(k)]

    def local_mask(idx):
        # true-coordinate domain mask of this device's slab
        shape = (slab,) + (n,) * (m - 1)
        coords = [
            jax.lax.broadcasted_iota(jnp.int32, shape, j)
            for j in range(m)
        ]
        coords[0] = coords[0] + idx * slab
        if m == 2:
            return coords[1] <= coords[0]
        total = coords[0]
        for c in coords[1:]:
            total = total + c
        return total < n

    def _step(local):
        idx = jax.lax.axis_index(axis)
        msk = local_mask(idx)
        s = jnp.where(msk, local, 0)
        # seam halo: one element plane each way along the sharded axis
        up = jax.lax.ppermute(s[-1:], axis, fwd)    # prev shard's base plane
        down = jax.lax.ppermute(s[:1], axis, bwd)   # next shard's apex plane
        if not periodic:
            up = jnp.where(idx == 0, 0, up)
            down = jnp.where(idx == k - 1, 0, down)
        padded = jnp.concatenate([up, s, down], axis=0)
        # remaining axes are fully local: wrap (m=2) or zero-pad (m>=3)
        for ax in range(1, m):
            if periodic:
                lo = jax.lax.slice_in_dim(padded, n - 1, n, axis=ax)
                hi = jax.lax.slice_in_dim(padded, 0, 1, axis=ax)
            else:
                shape = list(padded.shape)
                shape[ax] = 1
                lo = hi = jnp.zeros(shape, padded.dtype)
            padded = jnp.concatenate([lo, padded, hi], axis=ax)
        neigh = jnp.zeros_like(s)
        for shift in np.ndindex(*(3,) * m):
            if all(d == 1 for d in shift):
                continue
            sl = tuple(
                slice(d, d + dim) for d, dim in zip(shift, s.shape)
            )
            neigh = neigh + padded[sl]
        born = (s == 0) & (neigh == 3)
        survive = (s == 1) & ((neigh == 2) | (neigh == 3))
        new = (born | survive).astype(local.dtype)
        # engine semantics: out-of-domain elements keep their input value
        return jnp.where(msk, new, local)

    fn = jax.jit(
        jax.shard_map(_step, mesh=mesh, in_specs=spec, out_specs=spec,
                      check_vma=False)
    )
    _SPMD_CACHE[key] = fn
    return fn


def sharded_ca(state, k: int, steps: int = 1, *, rho: Optional[int] = None,
               kind: str = "hmap", mesh=None, executor: str = "engine",
               interpret=None):
    """Run ``steps`` sharded CA generations on an ``(n,)*m`` state.

    Convenience wrapper over ``ShardedSimplexCA`` — bit-equal to
    ``steps`` applications of the single-device engine CA
    (``kernels.engine.ca`` / ``ca_md``).

    Args:
        state: ``(n,)*m`` 0/1 array (m = state.ndim >= 2).
        k: Shard count.
        steps: Generations to run.
        rho: Engine tile side (engine executor).
        kind: Base schedule kind.
        mesh: Mesh from ``shard_mesh`` (None = default device only).
        executor: ``'engine'`` or ``'spmd'``.
        interpret: Pallas mode (None = per-backend policy).

    Returns:
        The final generation, same shape/dtype as ``state``.
    """
    runner = ShardedSimplexCA(
        state.ndim, state.shape[0], k, rho=rho, kind=kind, mesh=mesh,
        interpret=interpret,
    )
    return runner.run(state, steps, executor=executor)
