"""Grid schedules: how a Pallas/TPU grid walks an m-simplex domain.

This is the hardware-adaptation layer (DESIGN.md §2): on TPU the paper's
"thread map" becomes the *grid -> data-tile schedule*, realized either as
pure index arithmetic inside a ``BlockSpec.index_map`` (the faithful H
form) or as small scalar-prefetch coordinate tables (the TPU-idiomatic
exact form — one int32 per block, fetched by the scalar core, negligible
next to tile compute).

``SimplexSchedule(m, n, kind)`` is the one entry point (DESIGN.md §2.2):
a registry keyed by (dimension, kind) resolves the walk, and every
schedule exposes the same surface —

    .grid    grid dimensions the kernel launches (tuple)
    .steps   total grid steps (the paper's "parallel space")
    .useful  simplex cells the walk must cover, V(Delta^m_n)
    .map     (*w) -> (*coords, valid); dual-backend (numpy / jax tracers)
    .table() host-side (steps, m+1) int32 walk table for inspection
    .waste() steps/useful - 1, the measured extra parallel space
    .exact   True when no grid step is invalid (read via ``every_step_valid``)

Registered kinds
----------------
* m=2: ``hmap`` (zero-waste H grid), ``rb`` (RB fold [37]), ``bb``
  (bounding box + predicate), ``table`` (scalar-prefetch exact walk),
  ``composite`` (general-n trapezoid/shell pieces, zero waste at m=2).
* m=3: ``hmap``/``octant`` (r=1/2, beta=3 recursion, ~20% waste),
  ``table`` (0% waste), ``bb``, ``composite`` (any n, analytical).
* m>=4: ``hmap`` (orthant recursion, (1/r, beta) from
  ``general_m.best_r_beta(m, constructible=True)``), ``table``, ``bb``,
  ``composite``.

``composite`` (DESIGN.md §4.2) serves *arbitrary* n at every m: the
simplex decomposes into a power-of-two core plus sheared-prism shell
pieces (core/trapezoids.py), concatenated into one linear grid whose
map is pure index arithmetic — host-side construction is O(pieces),
never the O(V) enumeration the ``table`` kind pays.

``folded_causal_pairs`` — the load-balanced causal sequence-parallel
partition: query-tile i pairs with n-1-i so every pair owns (n+1) KV
tiles — equal triangle *area* per shard.  This is the paper's
parallel-space-balancing argument applied to sharding.
"""

from __future__ import annotations

import warnings
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional, Tuple

import numpy as np

from . import hmap as H
from .general_m import alpha_extra_space, best_r_beta
from .simplex import enumerate_simplex, simplex_volume, tet, tri
from .trapezoids import composite_map, decompose_simplex, piece_map

__all__ = [
    "SimplexSchedule",
    "register_schedule",
    "registered_kinds",
    "resolve_kind",
    "step_grid_indices",
    "every_step_valid",
    "Schedule2D",
    "schedule2d_table",
    "schedule3d_table",
    "folded_causal_pairs",
    "grid_steps",
]


def every_step_valid(sched) -> bool:
    """Whether no grid step of ``sched`` can be invalid, known statically.

    The engine appends a trash tile for invalid steps to park their
    write-back on; a walk with none needs no trash tile
    (``kernels.engine._launch_domain``).  The answer is the schedule's
    ``exact`` attribute: ``steps == useful`` for a registered kind or a
    composite piece (the valid steps cover all ``useful`` blocks, so a
    walk of exactly that many steps has no room for an invalid one), the
    base walk's answer for a ``ShardSchedule``.  A schedule surface that
    does not state it counts as possibly invalid.

    Args:
        sched: Any schedule surface.

    Returns:
        True when every step of the walk is valid.

    Example:
        >>> every_step_valid(SimplexSchedule(2, 4, "hmap"))
        True
        >>> every_step_valid(SimplexSchedule(2, 4, "bb"))
        False
    """
    return bool(getattr(sched, "exact", False))


def step_grid_indices(sched) -> Tuple[np.ndarray, ...]:
    """Per-axis grid indices of every step — the pass-visible enumeration.

    The static-analysis passes (``repro.analysis``, DESIGN.md §9) replay
    a schedule's walk without launching Pallas by feeding these arrays
    straight into ``sched.map`` — exactly the linearization the kernels
    use (grid axis 0 fastest; for m=2 grids ``(w, h)``: wy-major, wx
    within).  Works for any object with the schedule surface
    (``SimplexSchedule``, ``_PieceSchedule``, ``ShardSchedule``).

    Args:
        sched: Any schedule exposing ``.grid`` and ``.steps``.

    Returns:
        One int64 array of length ``sched.steps`` per grid axis.

    Example:
        >>> ws = step_grid_indices(SimplexSchedule(2, 4, "hmap"))
        >>> len(ws), ws[0].shape
        (2, (10,))
    """
    lin = np.arange(sched.steps, dtype=np.int64)
    ws = []
    for g in sched.grid:
        ws.append(lin % g)
        lin = lin // g
    return tuple(ws)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class _Spec:
    """Resolved schedule: what a kernel needs to launch the walk."""

    grid: Tuple[int, ...]
    map_fn: Callable  # (*w[, tab_ref]) -> (*coords, valid)
    useful: int
    # lazy scalar-prefetch payload for table-driven walks, else None —
    # a thunk so that reading .steps/.waste() never pays the O(V)
    # enumeration (grid_steps on large n must stay arithmetic)
    table_builder: Optional[Callable[[], np.ndarray]] = field(default=None)
    # asymptotic extra-space fraction of this kind (inf-n limit), if known
    alpha: Optional[float] = field(default=None)


# (m | None, kind) -> builder(m, n) -> _Spec.  None entries serve any m
# without an exact (m, kind) registration (the general-m fallbacks).
_REGISTRY: Dict[Tuple[Optional[int], str], Callable[[int, int], _Spec]] = {}


def register_schedule(m: Optional[int], kind: str):
    """Register a schedule builder for a (dimension, kind) pair.

    Args:
        m: Exact dimension the builder serves, or ``None`` to register a
            dimension-generic fallback used by any m without an exact
            ``(m, kind)`` entry.
        kind: Schedule kind name (e.g. ``"hmap"``, ``"composite"``).

    Returns:
        A decorator that records ``builder(m, n) -> _Spec`` in the
        registry and returns it unchanged.  Usage::

            @register_schedule(None, "mykind")
            def _build_mykind(m, n) -> _Spec: ...

    Example:
        >>> "hmap" in registered_kinds(2)  # builders self-register at import
        True
    """

    def _deco(builder):
        _REGISTRY[(m, kind)] = builder
        return builder

    return _deco


def registered_kinds(m: int) -> Tuple[str, ...]:
    """Kinds available for dimension m (exact + generic registrations).

    Args:
        m: Simplex dimension.

    Returns:
        Sorted tuple of kind names ``SimplexSchedule(m, n, kind)``
        accepts at this dimension.

    Example:
        >>> registered_kinds(4)
        ('bb', 'composite', 'hmap', 'table')
    """
    kinds = {k for mm, k in _REGISTRY if mm == m or mm is None}
    return tuple(sorted(kinds))


def resolve_kind(m: int, n: int, kind: str, backend: Optional[str] = None) -> str:
    """Kernel-facing kind resolution (the §4.1 power-of-two constraint).

    'hmap' requires a power-of-two tile count.  For non-pow2 n the
    analytical answer is the §4.2 decomposition: at m >= 3 the requested
    recursion resolves to ``'composite'`` — the general-n piecewise map
    (core/trapezoids.py), one linear grid, O(pieces) host-side cost.  At
    m = 2 the dedicated (w, h)-grid kernels instead fall back to RB
    (exact for any even n) or BB (odd n); the m=2 composite kind exists
    for linear-grid consumers and analysis.

    ``kind='auto'`` delegates to the ``repro.autotune`` subsystem
    (DESIGN.md §5): the schedule is picked per (m, n, backend) from the
    roofline cost model plus any recorded ``BENCH_maps.json``
    measurements, and the decision is cached on disk — kernels and
    benchmarks never hand-pick a schedule.

    Args:
        m: Simplex dimension of the kernel's domain.
        n: Tile count per side (the kernel-facing problem size).
        kind: Requested schedule kind, or ``'auto'``.
        backend: Backend name for autotuned resolution (None = active).

    Returns:
        The kind actually constructible at this (m, n) — ``kind`` itself
        whenever it is exact there.

    Example:
        >>> resolve_kind(3, 6, "hmap"), resolve_kind(4, 100, "hmap")
        ('composite', 'composite')
        >>> resolve_kind(4, 16, "hmap"), resolve_kind(2, 6, "hmap")
        ('hmap', 'rb')
    """
    if kind == "auto":
        from repro.autotune import choose_kind

        kind = choose_kind(m, n, backend=backend).kind
    pow2 = n >= 2 and (n & (n - 1)) == 0
    if m == 2:
        if kind == "hmap" and not pow2:
            kind = "rb" if n % 2 == 0 else "bb"
        if kind == "rb" and n % 2 != 0:
            kind = "bb"
        return kind
    if kind in ("hmap", "octant") and not pow2:
        return "composite"
    return kind


class SimplexSchedule:
    """A grid walk over the discrete m-simplex of side n (in tile units).

    The unified scheduling layer: 2-simplex, 3-simplex and general-m
    walks behind one registry-based API (module docstring for the kind
    table).  Kernel-side, ``.grid``/``.map`` plug straight into Pallas
    ``grid=``/``BlockSpec.index_map``; table-driven kinds additionally
    ship ``.prefetch`` through ``PrefetchScalarGridSpec`` and their
    ``.map`` takes the prefetched ref as a trailing argument.

    Args (constructor):
        m: Simplex dimension, m >= 2.
        n: Side length in tile units (any n >= 1 for ``composite``/
            ``table``/``bb``; power-of-two for the ``hmap`` recursions).
        kind: Registered kind name; see ``registered_kinds(m)``.

    Example:
        >>> sched = SimplexSchedule(3, 6, "composite")  # non-pow2 n
        >>> sched.steps, sched.useful, round(sched.waste(), 3)
        (72, 56, 0.286)
        >>> tab = sched.table()  # (steps, m+1): (*coords, valid)
        >>> tab.shape
        (72, 4)
    """

    def __init__(self, m: int, n: int, kind: str = "hmap"):
        builder = _REGISTRY.get((m, kind)) or _REGISTRY.get((None, kind))
        if builder is None or m < 2:
            raise ValueError(
                f"no schedule registered for m={m}, kind={kind!r}; "
                f"available: {registered_kinds(m) if m >= 2 else ()}"
            )
        self.m = m
        self.n = n
        self.kind = kind
        self._spec = builder(m, n)
        self._table_cache: Optional[np.ndarray] = None

    # -- launch surface ----------------------------------------------------

    @property
    def grid(self) -> Tuple[int, ...]:
        """Grid dimensions to launch (``(w, h)`` for 2-D walks, else linear)."""
        return self._spec.grid

    @property
    def steps(self) -> int:
        """Total grid steps — the paper's "parallel space" (O(1) arithmetic)."""
        s = 1
        for g in self._spec.grid:
            s *= g
        return s

    @property
    def useful(self) -> int:
        """Simplex cells the walk must cover, ``V(Delta^m_n)``."""
        return self._spec.useful

    @property
    def needs_table(self) -> bool:
        """True when this kind walks a host-built scalar-prefetch table."""
        return self._spec.table_builder is not None

    @property
    def prefetch(self) -> Optional[np.ndarray]:
        """Scalar-prefetch payload for table-driven walks (else None).

        Built lazily on first access and cached — `.steps`/`.waste()`
        stay O(1) arithmetic even for table kinds at large n.
        """
        if self._spec.table_builder is None:
            return None
        if self._table_cache is None:
            self._table_cache = self._spec.table_builder()
        return self._table_cache

    def map(self, *w):
        """Map grid coordinates to data-tile coordinates.

        Args:
            *w: One index/array per grid axis (fastest axis first); for
                table-driven kinds, the prefetched table ref last.

        Returns:
            ``(*coords, valid)`` — m data coordinates plus the validity
            flag.  Dual-backend (numpy arrays or jax tracers).

        Example:
            >>> import numpy as np
            >>> s = SimplexSchedule(2, 4, "hmap")
            >>> x, y, v = s.map(np.arange(2), np.zeros(2, np.int64))
            >>> x.tolist(), y.tolist(), v.tolist()
            ([0, 1], [0, 1], [True, True])
        """
        return self._spec.map_fn(*w)

    # -- accounting --------------------------------------------------------

    def waste(self) -> float:
        """Measured extra parallel space at this n.

        Returns:
            ``steps/useful - 1`` — 0.0 for exact (zero-waste) walks.

        Example:
            >>> SimplexSchedule(2, 100, "composite").waste()
            0.0
        """
        return self.steps / self.useful - 1.0

    @property
    def exact(self) -> bool:
        """No grid step is invalid: the walk has no extra parallel space."""
        return self.steps == self.useful

    def asymptotic_waste(self) -> Optional[float]:
        """inf-n extra-space fraction of this kind (None if unknown).

        Returns:
            The registered alpha: exact limit for single-map kinds, an
            upper bound for ``composite`` (whose measured waste at
            non-pow2 n is typically far lower — the shell pieces are
            lower-dimensional).
        """
        return self._spec.alpha

    # -- host-side enumeration ---------------------------------------------

    def table(self) -> np.ndarray:
        """(steps, m+1) int32 walk table: (*coords, valid) per grid step.

        Step order matches the linearization kernels use: grid axis 0
        fastest (for m=2 grids (w, h): wy-major, wx within).
        """
        if self.needs_table:
            tab = self.prefetch
            valid = np.ones((len(tab), 1), dtype=np.int32)
            return np.concatenate([tab.astype(np.int32), valid], axis=1)
        ws = step_grid_indices(self)
        out = self.map(*ws)
        coords, valid = out[:-1], out[-1]
        cols = [np.asarray(c) for c in coords]
        cols.append(np.asarray(valid).astype(np.int64))
        return np.stack(cols, axis=1).astype(np.int32)

    # -- per-piece launch splitting (composite only) -----------------------

    def split_pieces(self) -> Tuple["object", ...]:
        """Per-piece sub-schedules of a composite walk.

        A composite schedule's branchless map decodes every piece per
        evaluated index (O(pieces) selects per grid step).  Splitting
        returns one lightweight schedule per piece — same ``.grid`` /
        ``.steps`` / ``.map`` surface, each map decoding only its own
        factor chain — so a kernel can launch one ``pallas_call`` per
        piece when the select chain would dominate
        (``repro.autotune.should_split_pieces`` is the heuristic).

        Returns:
            Tuple of per-piece schedules for ``kind='composite'``;
            ``(self,)`` for every other kind (nothing to split).

        Example:
            >>> subs = SimplexSchedule(3, 6, "composite").split_pieces()
            >>> sum(s.steps for s in subs)
            72
        """
        if self.kind != "composite":
            return (self,)
        pieces = decompose_simplex(self.m, self.n)
        return tuple(
            _PieceSchedule(self.m, self.n, p, i) for i, p in enumerate(pieces)
        )

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (
            f"SimplexSchedule(m={self.m}, n={self.n}, kind={self.kind!r}, "
            f"grid={self.grid}, steps={self.steps}, useful={self.useful})"
        )


class _PieceSchedule:
    """One piece of a split composite schedule (see ``split_pieces``).

    Exposes the subset of the ``SimplexSchedule`` surface kernels
    consume for linear walks: ``.grid``, ``.steps``, ``.useful``,
    ``.map`` (piece-local linear index -> global coords + valid) and a
    ``.prefetch`` that is always None (pure index arithmetic).
    """

    kind = "composite-piece"

    def __init__(self, m: int, n: int, piece, index: int):
        self.m = m
        self.n = n
        self.piece = piece
        self.index = index
        self.grid = (piece.grid_cells,)
        self.steps = piece.grid_cells
        self.useful = piece.data_cells
        self.prefetch = None

    @property
    def exact(self) -> bool:
        """No grid step of this piece is invalid."""
        return self.steps == self.useful

    def map(self, lin):
        """Piece-local linear index -> ``(*coords, valid)`` (global)."""
        out = piece_map(self.piece, self.m, lin)
        if self.m != 2:
            return out
        u, v, ok = out
        return u, (self.n - 1) - v, ok  # match the m=2 composite flip

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (
            f"_PieceSchedule(m={self.m}, n={self.n}, piece={self.index}, "
            f"steps={self.steps})"
        )


def _ones_like(x):
    if type(x).__module__.startswith("jax"):
        import jax.numpy as jnp

        return jnp.ones_like(x, dtype=bool)
    return np.ones_like(np.asarray(x), dtype=bool)


# ---------------------------------------------------------------------------
# 2-simplex builders
# ---------------------------------------------------------------------------


@register_schedule(2, "hmap")
def _build2_hmap(m: int, n: int) -> _Spec:
    assert n >= 2 and (n & (n - 1)) == 0, (
        "hmap needs power-of-two n (paper §4.1); use the trapezoid "
        "decomposition (§4.2) for general n"
    )

    def fn(wx, wy):
        x, y = H.hmap2_full(wx, wy, n)
        return x, y, _ones_like(x)

    return _Spec((n // 2, n + 1), fn, tri(n), alpha=0.0)


@register_schedule(2, "rb")
def _build2_rb(m: int, n: int) -> _Spec:
    assert n % 2 == 0 and n >= 2

    def fn(wx, wy):
        from .maps_baseline import rb_map2

        x, y = rb_map2(wx, wy, n)
        return x, y, _ones_like(x)

    return _Spec((n // 2, n + 1), fn, tri(n), alpha=0.0)


@register_schedule(2, "bb")
def _build2_bb(m: int, n: int) -> _Spec:
    def fn(wx, wy):
        return wx, wy, wx <= wy

    return _Spec((n, n), fn, tri(n), alpha=1.0)


@register_schedule(2, "table")
def _build2_table(m: int, n: int) -> _Spec:
    def fn(lin, tab_ref):
        return tab_ref[lin, 0], tab_ref[lin, 1], _one(lin)

    return _Spec(
        (tri(n),), fn, tri(n),
        table_builder=lambda: schedule2d_table(n), alpha=0.0,
    )


# ---------------------------------------------------------------------------
# 3-simplex builders
# ---------------------------------------------------------------------------


@register_schedule(3, "table")
def _build3_table(m: int, n: int) -> _Spec:
    def fn(lin, tab_ref):
        return tab_ref[lin, 0], tab_ref[lin, 1], tab_ref[lin, 2], _one(lin)

    return _Spec(
        (tet(n),), fn, tet(n),
        table_builder=lambda: schedule3d_table(n), alpha=0.0,
    )


# ---------------------------------------------------------------------------
# general-m builders (serve m=3 'hmap'/'octant' and every m >= 4)
# ---------------------------------------------------------------------------


def _build_md_hmap(m: int, n: int) -> _Spec:
    inv_r, beta = best_r_beta(m, constructible=True)
    steps = H.hmap_m_grid_size(n, m, inv_r, beta)

    def fn(lin):
        return H.hmap_m_recursive(lin, n, m, inv_r, beta)

    return _Spec(
        (steps,),
        fn,
        simplex_volume(n, m),
        alpha=alpha_extra_space(m, inv_r, beta),
    )


register_schedule(None, "hmap")(_build_md_hmap)
register_schedule(3, "octant")(_build_md_hmap)


@register_schedule(None, "table")
def _build_md_table(m: int, n: int) -> _Spec:
    def fn(lin, tab_ref):
        return tuple(tab_ref[lin, j] for j in range(m)) + (_one(lin),)

    v = simplex_volume(n, m)
    return _Spec(
        (v,), fn, v,
        table_builder=lambda: enumerate_simplex(n, m).astype(np.int32),
        alpha=0.0,
    )


@register_schedule(None, "composite")
def _build_composite(m: int, n: int) -> _Spec:
    """General-n composite schedule: pow2 core + shell pieces, one grid.

    Pieces from ``trapezoids.decompose_simplex`` are concatenated into a
    single linear grid; the map selects the piece by static prefix
    offsets and decodes its power-of-two factor chain (all index
    arithmetic — usable as a Pallas index_map, no scalar prefetch).  At
    m=2 the strict-sum coordinates are flipped into the repo's
    (col, row) lower-triangle convention; every m=2 factor has dim <= 2
    so the m=2 composite is exactly zero waste (the trapezoid scheme).
    For m >= 3 the asymptotic extra space is bounded by the core
    recursion's alpha; measured waste at non-pow2 n sits well below it
    (shell pieces are lower-dimensional).
    """
    pieces = decompose_simplex(m, n)
    steps = sum(p.grid_cells for p in pieces)

    if m == 2:

        def fn(lin):
            u, v, ok = composite_map(pieces, 2, lin)
            return u, (n - 1) - v, ok  # strict (u, v) -> (col, row)

    else:

        def fn(lin):
            return composite_map(pieces, m, lin)

    alpha = 0.0 if m == 2 else alpha_extra_space(m, 2, m)
    return _Spec((steps,), fn, simplex_volume(n, m), alpha=alpha)


@register_schedule(None, "bb")
def _build_md_bb(m: int, n: int) -> _Spec:
    import math

    def fn(lin):
        coords = []
        rem = lin
        for _ in range(m):
            coords.append(rem % n)
            rem = rem // n
        total = coords[0]
        for c in coords[1:]:
            total = total + c
        return tuple(coords) + (total < n,)

    return _Spec(
        (n**m,), fn, simplex_volume(n, m), alpha=math.factorial(m) - 1.0
    )


def _one(lin):
    if type(lin).__module__.startswith("jax"):
        import jax.numpy as jnp

        return jnp.ones_like(jnp.asarray(lin), dtype=jnp.bool_)
    return np.ones_like(np.asarray(lin), dtype=bool)


# ---------------------------------------------------------------------------
# deprecated 2D shim + host tables
# ---------------------------------------------------------------------------


class Schedule2D:
    """Deprecated thin shim over ``SimplexSchedule(2, n, kind)``.

    kind='hmap':  zero-waste (n/2, n+1) grid, paper Eq. 14-16 + our
                  diagonal rows; tile = (col, row) with col <= row.
    kind='rb':    zero-waste (n/2, n+1) grid, RB fold [37].
    kind='bb':    (n, n) bounding box + validity predicate (the baseline).
    """

    def __init__(self, n: int, kind: str = "hmap"):
        warnings.warn(
            "Schedule2D is deprecated; use SimplexSchedule(2, n, kind)",
            DeprecationWarning,
            stacklevel=2,
        )
        assert kind in ("hmap", "rb", "bb")
        self.n = n
        self.kind = kind
        self._s = SimplexSchedule(2, n, kind)

    @property
    def grid(self) -> Tuple[int, int]:
        """(width, height) of the delegated ``SimplexSchedule(2, ...)``."""
        return self._s.grid

    @property
    def steps(self) -> int:
        """Total grid steps of the delegated schedule."""
        return self._s.steps

    @property
    def useful(self) -> int:
        """Lower-triangle tiles to cover, ``tri(n)``."""
        return self._s.useful

    def map(self, wx, wy):
        """Delegate to ``SimplexSchedule.map``: (wx, wy) -> (x, y, valid)."""
        return self._s.map(wx, wy)

    def table(self) -> np.ndarray:
        """Delegate to ``SimplexSchedule.table()``."""
        return self._s.table()


def schedule2d_table(n: int) -> np.ndarray:
    """Exact (tri(n), 2) int32 table of lower-triangle tiles, diagonal-first
    order (diagonal tiles first so masked tiles are contiguous).

    Args:
        n: Tile count per side.

    Returns:
        ``(tri(n), 2)`` int32 array of (col, row) pairs — the O(V)
        scalar-prefetch payload of the m=2 ``table`` kind.

    Example:
        >>> schedule2d_table(2).tolist()
        [[0, 0], [1, 1], [0, 1]]
    """
    cols, rows = [], []
    for y in range(n):
        cols.append(y)
        rows.append(y)
    for y in range(n):
        for x in range(y):
            cols.append(x)
            rows.append(y)
    return np.stack([np.array(cols), np.array(rows)], 1).astype(np.int32)


def schedule3d_table(n: int) -> np.ndarray:
    """Exact (tet(n), 3) int32 table of T(n) tiles (zero waste, the
    TPU-idiomatic scalar-prefetch form).

    Args:
        n: Tile count per side.

    Returns:
        ``(tet(n), 3)`` int32 array of (x, y, z) with x+y+z < n, x
        fastest — the O(V) scalar-prefetch payload of the m=3 ``table``
        kind.

    Example:
        >>> schedule3d_table(2).tolist()
        [[0, 0, 0], [1, 0, 0], [0, 1, 0], [0, 0, 1]]
    """
    pts = []
    for z in range(n):
        for y in range(n - z):
            for x in range(n - z - y):
                pts.append((x, y, z))
    arr = np.asarray(pts, dtype=np.int32)
    assert len(arr) == tet(n)
    return arr


def folded_causal_pairs(n_tiles: int) -> np.ndarray:
    """Folded pairs (i, n-1-i): the equal-area causal partition.

    Each pair owns ``i+1 + n-i = n+1`` KV tiles — the load-balanced
    fold used for sequence-parallel sharding and by the flash kernel's
    folded grid (its k-way generalization to any dimension is
    ``distributed.simplex_sharding.fold_partition``).  An odd tile
    count self-pairs the middle tile: the last row is ``[mid, mid]``
    and owns only ``mid+1`` KV tiles — callers that require the
    constant ``n+1``-tile balance (the folded flash grid) must reject
    odd counts instead of consuming the short row.

    Args:
        n_tiles: Number of query tiles, >= 1.

    Returns:
        ``(ceil(n_tiles/2), 2)`` int32 array of folded query-tile
        pairs; for odd ``n_tiles`` the final row is the self-paired
        middle tile.

    Example:
        >>> folded_causal_pairs(4).tolist()
        [[0, 3], [1, 2]]
        >>> folded_causal_pairs(5).tolist()
        [[0, 4], [1, 3], [2, 2]]
    """
    if n_tiles < 1:
        raise ValueError(f"n_tiles must be >= 1, got {n_tiles}")
    i = np.arange((n_tiles + 1) // 2, dtype=np.int32)
    return np.stack([i, n_tiles - 1 - i], 1)


def grid_steps(n: int, kind: str, m: int = 2) -> int:
    """Grid steps each schedule launches — the paper's 'parallel space'.

    The MAP-test speedup claim is the BB/steps ratio of these numbers.

    Args:
        n: Tile count per side.
        kind: Registered kind, or ``"paper"`` at m=3 for the literal
            Eq. 26 grid shape.
        m: Simplex dimension (default 2).

    Returns:
        Total grid steps of ``SimplexSchedule(m, n, kind)``.

    Example:
        >>> grid_steps(16, "hmap"), grid_steps(16, "bb")
        (136, 256)
    """
    if m == 3 and kind == "paper":
        w, h, d = H.hmap3_paper_grid_shape(n)
        return w * h * d
    return SimplexSchedule(m, n, kind).steps
