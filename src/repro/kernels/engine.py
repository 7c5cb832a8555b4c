"""The dimension-generic ``SimplexKernel`` engine (DESIGN.md §2.3).

One launcher serves every simplex workload at every dimension: a kernel
*body* (a small class declaring its halo stencil and per-tile compute —
MAP / ACCUM / EDM / CA ship here) is combined with any
``core.schedule.SimplexSchedule`` and lowered to a single
``pl.pallas_call`` per launch piece.  What used to be one hand-written
``pallas_call`` per (body, dimension) — EDM only at m=2, CA only at
m in {2, 3} — is now one generic construction, so the missing siblings
(``edm3d``, ``edm_md``, ``ca_md``) are O(1)-effort body registrations
rather than new kernels.

The engine owns every TPU-facing convention the hand-rolled kernels
established, dimension-generically:

* **Grid handling** — multi-axis grids (the m=2 ``(w, h)`` kinds) and
  linear grids (everything else) through the same index-map builder;
  table-driven kinds ship their payload via
  ``PrefetchScalarGridSpec``.
* **Trash tile** — when the launch plan can hold an invalid grid step,
  the domain array is padded by one tile row along axis 0 and invalid
  steps park there, so Pallas' end-of-step block flush never clobbers
  live data; a plan whose every step is valid launches on the seed
  array itself.  In-place semantics come from input/output aliasing of
  the body's *seed* array.
* **3^m halo subsystem** — bodies that declare ``halo = True`` receive
  the 3^m tiles fetched by shifted input refs (the standard Pallas
  stencil pattern — no element-offset reads on TPU), each masked by
  the domain predicate of its own position, and read element-shifted
  windows of them built from tile rotates and edge selects.
  Boundary handling is per body and dimension: ``'periodic'`` wraps
  block coordinates mod nb (the 2-simplex CA convention), ``'free'``
  clamps reads at the domain edge and masks by true coordinates (the
  m >= 3 convention).
* **Execution policy** — ``interpret=None`` resolves through
  ``kernels/policy.py`` per backend; block shapes are checked against
  the Mosaic tiling contract before compiled launches, and compiled
  launches whose double-buffered blocks outgrow Mosaic's default
  scoped VMEM ask for a limit sized to them.
* **Launch splitting** — ``kind='composite'`` schedules can launch one
  ``pallas_call`` per piece (``split=``, autotuned default); the engine
  refuses the split for halo bodies, whose neighbor reads make
  per-piece chaining unsound.
* **Explicit schedules** — ``SimplexKernel(..., schedule=s)`` launches
  any object with the schedule surface (``.grid``/``.map``/
  ``.prefetch``) instead of resolving a kind: the per-shard execution
  path of ``distributed/simplex_sharding.py`` (DESIGN.md §7), where
  each device walks one ``ShardSchedule`` of the folded partition.
* **Compiled fallback** — ``executor='xla'`` routes to the fused-XLA
  executors in ``kernels/compiled.py`` where one exists (ACCUM, MAP),
  the compiled path on hosts whose Pallas backend can only interpret.

Every ``pl.pallas_call`` in the package is constructed here or in
``kernels/compiled.py``; other modules launch through
``pallas_launch`` (enforced by an AST test in
``tests/test_compiled.py``).  The hand-rolled originals survive
verbatim in ``kernels/legacy.py`` as the differential baseline for
``tests/test_engine_parity.py``; the public entry points in
``kernels/simplex_kernels.py`` are deprecated shims over this module.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.schedule import SimplexSchedule, every_step_valid, resolve_kind

from .policy import (
    aligned_rho,
    check_tile_alignment,
    resolve_interpret,
    vmem_limit_bytes,
)

__all__ = [
    "SimplexKernel",
    "KernelBody",
    "BodyContext",
    "register_body",
    "registered_bodies",
    "get_body",
    "pallas_launch",
    "domain_mask",
    "halo_shifts",
    "launch_shifts",
    "out_block_transform",
    "shift_block_transform",
    "map_table",
    "accum",
    "edm",
    "ca",
    "edm2d",
    "edm3d",
    "edm_md",
    "ca_md",
    "accum_md",
    "default_rho",
]


# ---------------------------------------------------------------------------
# the one pallas_call front door
# ---------------------------------------------------------------------------


def pallas_launch(kernel, *, interpret: Optional[bool] = None, **kwargs):
    """Construct a ``pl.pallas_call`` with the resolved execution policy.

    The single sanctioned way to launch Pallas outside this module and
    ``kernels/compiled.py`` (AST-enforced): ``interpret=None`` resolves
    through ``policy.resolve_interpret`` (CPU interprets, TPU/GPU
    compile, ``REPRO_INTERPRET`` overrides); all other keyword
    arguments pass through to ``pl.pallas_call`` unchanged.

    Args:
        kernel: The Pallas kernel function.
        interpret: Execution mode; ``None`` resolves per backend.
        **kwargs: Forwarded to ``pl.pallas_call``.

    Returns:
        The callable returned by ``pl.pallas_call``.
    """
    return pl.pallas_call(
        kernel, interpret=resolve_interpret(interpret), **kwargs
    )


# ---------------------------------------------------------------------------
# geometry helpers shared by every body
# ---------------------------------------------------------------------------


def default_rho(m: int, interpret: Optional[bool] = None) -> int:
    """The per-dimension default tile side, aligned for the launch mode.

    Args:
        m: Simplex dimension.
        interpret: Pallas mode (None = policy default).

    Returns:
        Under interpret mode 8 at m=2, 4 at m=3, 2 at m >= 4 — small
        enough that the test sweeps stay fast.  On the compiled path the
        same sides rounded up by ``policy.aligned_rho`` to the 128-lane
        tile Mosaic accepts.
    """
    return aligned_rho({2: 8, 3: 4}.get(m, 2), interpret)


def domain_mask(m: int, n: int, coords: Sequence) -> jax.Array:
    """The per-element domain predicate in array-axis order.

    Args:
        m: Simplex dimension.
        n: Side length in elements.
        coords: One global coordinate array per array axis (axis 0
            first — axis j holds math coordinate ``x_{m-1-j}``).

    Returns:
        Boolean mask: the m=2 inclusive lower triangle
        ``{col <= row}``, or the strict simplex ``{sum < n}`` at
        m >= 3 — the repo-wide domain conventions (DESIGN.md §2.2).
    """
    if m == 2:
        return coords[1] <= coords[0]
    total = coords[0]
    for c in coords[1:]:
        total = total + c
    return total < n


def halo_shifts(m: int) -> Tuple[Tuple[int, ...], ...]:
    """The full 3^m block-offset stencil at dimension m.

    Args:
        m: Simplex dimension.

    Returns:
        All ``(-1, 0, 1)^m`` offset tuples, lexicographic order —
        the neighborhood a ``halo = True`` body is assembled from.

    Example:
        >>> halo_shifts(2)[:3]
        ((-1, -1), (-1, 0), (-1, 1))
    """
    return tuple(itertools.product((-1, 0, 1), repeat=m))


def launch_shifts(body: "KernelBody", m: int) -> Tuple[Tuple[int, ...], ...]:
    """Block offsets the engine actually fetches for ``body`` at dim m.

    One shifted input ref is launched per offset: the full 3^m stencil
    for halo bodies, the centre alone otherwise.  The halo-conformance
    pass (``repro.analysis``, DESIGN.md §9) diffs this mechanism-side
    set against the body's *declared* ``stencil(m)``.

    Args:
        body: The kernel body.
        m: Simplex dimension.

    Returns:
        Offset tuples, centre ``(0,)*m`` always included.
    """
    return halo_shifts(m) if body.halo else ((0,) * m,)


def out_block_transform(nb: int, trash: bool) -> Callable:
    """The engine's output index-map transform: clip, and trash-tile park.

    Valid grid steps write their (clipped) block.  The trash row ``nb``
    exists only where the launch plan can hold an invalid step (not
    ``core.schedule.every_step_valid``): it is appended along axis 0 and
    invalid steps park there, so Pallas' end-of-step flush never
    clobbers live data.  A plan whose every step is valid has no trash
    row, and the transform only clips.  Shared by ``_launch_domain`` and
    the write-race pass in ``repro.analysis`` so the analyzer verifies
    the exact transform the launcher uses (DESIGN.md §9).

    Args:
        nb: Tile count per side (trash row index along axis 0).
        trash: Whether the launch has the trash row.

    Returns:
        ``transform(blocks, coords, valid) -> block index tuple`` in
        array-axis order.
    """

    def _t(blocks, coords, valid):
        clipped = tuple(jnp.clip(b, 0, nb - 1) for b in blocks)
        if not trash:
            return clipped
        return (jnp.where(valid, clipped[0], nb),) + clipped[1:]

    return _t


def shift_block_transform(d: Tuple[int, ...], nb: int, boundary: str,
                          trash: bool) -> Callable:
    """The engine's input index-map transform for stencil offset ``d``.

    ``'periodic'`` wraps block coordinates mod nb (the 2-simplex CA
    convention); ``'free'`` clamps at the domain edge and, where the
    launch has a trash row, parks invalid steps there (the m >= 3
    convention) — clamp duplicates are masked inert by true coordinates
    at assembly time.

    Args:
        d: Block offset, one entry per array axis.
        nb: Tile count per side.
        boundary: ``'periodic'`` or ``'free'``.
        trash: Whether the launch has the trash row (``out_block_transform``).

    Returns:
        ``transform(blocks, coords, valid) -> block index tuple``.
    """

    def _t(blocks, coords, valid):
        if boundary == "periodic":
            return tuple((b + dj) % nb for b, dj in zip(blocks, d))
        shifted = tuple(
            jnp.clip(b + dj, 0, nb - 1) for b, dj in zip(blocks, d)
        )
        if not trash:
            return shifted
        return (jnp.where(valid, shifted[0], nb),) + shifted[1:]

    return _t


def _axis_coords(blocks, rho: int, shape: Tuple[int, ...]):
    """Global element coordinates of a tile, one array per axis."""
    m = len(shape)
    return [
        blocks[j] * rho
        + jax.lax.broadcasted_iota(jnp.int32, shape, j)
        for j in range(m)
    ]


def _grid_spec(table, grid, in_specs, out_specs):
    """Plain grid or scalar-prefetch grid, matching the schedule kind."""
    if table is None:
        return (
            pl.GridSpec(grid=tuple(grid), in_specs=in_specs,
                        out_specs=out_specs),
            (),
        )
    from jax.experimental.pallas import tpu as pltpu

    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=tuple(grid),
        in_specs=in_specs,
        out_specs=out_specs,
    )
    return spec, (jnp.asarray(table),)


def _schedule(m: int, nb: int, kind: str) -> SimplexSchedule:
    """Engine-facing schedule resolution (no m=2 grid-shape restriction:
    linear-grid kinds like ``composite`` are first-class at every m)."""
    return SimplexSchedule(m, nb, resolve_kind(m, nb, kind))


def _launch_plan(m: int, nb: int, kind: str, split: Optional[bool],
                 element_local: bool, schedule=None):
    """Schedules to launch, one ``pallas_call`` each (DESIGN.md §5).

    Composite schedules may split into one launch per piece when the
    body is element-local (pieces cover disjoint tiles, so chaining
    launches through the aliased output is exact); halo bodies always
    launch the fused walk — a split piece would read neighbours the
    previous launch already stepped.

    An explicit ``schedule`` (e.g. a ``ShardSchedule`` from
    ``distributed/simplex_sharding.py``, DESIGN.md §7) bypasses kind
    resolution and piece splitting: the engine launches exactly the
    steps that schedule enumerates.
    """
    if schedule is not None:
        if schedule.m != m or schedule.n != nb:
            raise ValueError(
                f"explicit schedule is (m={schedule.m}, nb={schedule.n}) "
                f"but the launch needs (m={m}, nb={nb})"
            )
        return [schedule]
    sched = _schedule(m, nb, kind)
    if sched.kind == "composite" and element_local:
        subs = sched.split_pieces()
        if split is None:
            from repro.autotune import should_split_pieces

            split = should_split_pieces(len(subs), sched.steps)
        if split and len(subs) > 1:
            return list(subs)
    return [sched]


def _make_index_map(fn: Callable, transform: Callable) -> Callable:
    """Wrap a schedule map into a ``BlockSpec.index_map``.

    ``fn`` is ``SimplexSchedule.map`` — ``(*w[, tab_ref]) ->
    (*coords, valid)`` with math-order coordinates; ``transform`` maps
    ``(blocks, coords, valid)`` (blocks in array-axis order) to the
    block index tuple Pallas should fetch.
    """

    def _index_map(*args):
        out = fn(*args)
        coords, valid = out[:-1], out[-1]
        return transform(tuple(coords[::-1]), coords, valid)

    return _index_map


# ---------------------------------------------------------------------------
# body contract
# ---------------------------------------------------------------------------


@dataclass
class BodyContext:
    """Everything a body's per-tile compute sees (the engine fills it).

    Attributes:
        m: Simplex dimension.
        n: Domain side length in elements.
        nb: Tile count per side.
        rho: Tile side length.
        dtype: Output dtype.
        blocks: Traced block coordinates in array-axis order.
        valid: Traced schedule validity flag for this grid step.
        center: Raw center tile of the seed array (``(rho,)*m``) — also
            the out-of-domain fallback value the engine writes.
        neighbors: For ``halo = True`` bodies, the 3^m fetched tiles
            keyed by block offset, each masked by the domain predicate
            of its own position; None otherwise.  Read them through
            ``window``.
        extras: Tuple of refs for the body's extra operands.
    """

    m: int
    n: int
    nb: int
    rho: int
    dtype: object
    blocks: tuple
    valid: object
    center: object
    neighbors: object
    extras: tuple

    def coords(self):
        """Global element coordinates of this tile, per array axis."""
        return _axis_coords(self.blocks, self.rho, (self.rho,) * self.m)

    def mask(self):
        """In-domain-and-valid element mask of this tile."""
        return domain_mask(self.m, self.n, self.coords()) & self.valid

    def window(self, d: Tuple[int, ...]):
        """The ``(rho,)*m`` tile seen at element offset ``d``.

        Element ``i`` of the result is the masked halo value at
        ``i + d`` (each ``|d_j| <= 1``).  It is built from the centre
        tile and the neighbour tiles that offset reaches, each rotated
        by ``pltpu.roll`` and chosen per element by an edge select:
        only whole-tile rotates and selects, which Mosaic lowers on the
        chip (no dynamic slices of a larger assembly).

        Args:
            d: Element offset, one entry per array axis.

        Returns:
            The shifted view, same shape and dtype as the tiles.
        """
        return _window(self.neighbors, d, self.rho, self.m)


class KernelBody:
    """Base class of the body-registration contract (DESIGN.md §2.3).

    A body declares *what* one tile computes; the engine owns *where*
    tiles live (schedule walk, trash tile, aliasing, halo assembly,
    execution policy).  Subclasses set the class attributes and
    implement ``tile``; bodies with non-tile outputs (MAP) override
    ``launch`` wholesale.

    Class attributes:
        name: Registry key.
        halo: True to receive the 3^m neighbour tiles in
            ``BodyContext.neighbors`` (read through ``window``).
        element_local: True when per-piece launch splitting is sound
            (no cross-tile reads).
    """

    name: str = ""
    halo: bool = False
    element_local: bool = True

    # -- hooks ------------------------------------------------------------

    def boundary(self, m: int) -> str:
        """Halo boundary mode at dimension m: 'periodic' or 'free'."""
        return "periodic" if m == 2 else "free"

    def stencil(self, m: int) -> Tuple[Tuple[int, ...], ...]:
        """The block-offset stencil this body's compute declares it reads.

        Static-analysis metadata (DESIGN.md §9): the halo-conformance
        pass diffs this declaration against the blocks the engine's
        index maps actually fetch (``launch_shifts``).  The default is
        honest for the shipped bodies — full 3^m when ``halo`` is set,
        centre-only otherwise; a body whose ``tile`` reads fewer or
        more neighbours than the halo machinery supplies must override
        this so the declaration stays truthful.

        Args:
            m: Simplex dimension.

        Returns:
            Offset tuples, centre ``(0,)*m`` included.
        """
        return halo_shifts(m) if self.halo else ((0,) * m,)

    def seed(self, x, m: int):
        """(seed array, n): the domain-shaped array aliased to the
        output.  The default takes the operand itself (in-place
        semantics); EDM overrides with zeros."""
        n = x.shape[0]
        if x.shape != (n,) * m:
            raise ValueError(
                f"{self.name}: expected an m-cube operand of shape "
                f"{(n,) * m}, got {x.shape}"
            )
        return x, n

    def extra_arrays(self, x, m: int) -> tuple:
        """Extra operand arrays fetched per tile (default: none)."""
        return ()

    def extra_spec(self, a: int, x, m: int, nb: int, rho: int,
                   fn: Callable) -> pl.BlockSpec:
        """BlockSpec of extra operand ``a`` for the schedule map ``fn``."""
        raise NotImplementedError

    def tile(self, ctx: BodyContext):
        """The in-domain tile value (``(rho,)*m``); the engine writes
        ``where(ctx.mask(), tile, ctx.center)``."""
        raise NotImplementedError

    def launch(self, kernel: "SimplexKernel", x):
        """Run the body through the generic domain-array launcher."""
        return _launch_domain(kernel, self, x)

    def xla_executor(self, kernel: "SimplexKernel", x):
        """Fused-XLA fallback (``executor='xla'``); None if unavailable."""
        return None


# registry ------------------------------------------------------------------

_BODIES: Dict[str, KernelBody] = {}


def register_body(body: KernelBody) -> KernelBody:
    """Register a body instance under ``body.name``.

    Args:
        body: The ``KernelBody`` instance to register.

    Returns:
        The body, unchanged — usable as a decorator on instances.
    """
    _BODIES[body.name] = body
    return body


def registered_bodies() -> Tuple[str, ...]:
    """Sorted names of every registered body."""
    return tuple(sorted(_BODIES))


def get_body(body) -> KernelBody:
    """Resolve a body argument (name or instance) to the instance."""
    if isinstance(body, KernelBody):
        return body
    if body not in _BODIES:
        raise ValueError(
            f"no kernel body named {body!r}; registered: "
            f"{registered_bodies()}"
        )
    return _BODIES[body]


# ---------------------------------------------------------------------------
# the generic domain-array launcher
# ---------------------------------------------------------------------------


def _launch_domain(kernel: "SimplexKernel", body: KernelBody, x):
    """One launch per plan entry: trash-tile padding where a step can be
    invalid, index maps, halo assembly, aliased output — the engine core."""
    m, rho = kernel.m, kernel.rho
    seed, n = body.seed(x, m)
    if n % rho != 0:
        raise ValueError(f"{body.name}: rho={rho} must divide n={n}")
    interpret = resolve_interpret(kernel.interpret)
    check_tile_alignment((rho,) * m, interpret)
    nb = n // rho
    extras = body.extra_arrays(x, m)

    shifts = list(launch_shifts(body, m))
    centre_idx = shifts.index((0,) * m)
    boundary = body.boundary(m)

    plan = _launch_plan(m, nb, kernel.kind, kernel.split,
                        body.element_local and not body.halo,
                        schedule=kernel.schedule)
    # trash tile appended along axis 0 where a grid step can be invalid:
    # such steps park there.  Otherwise the kernel writes the seed in place.
    trash = not all(every_step_valid(sched) for sched in plan)
    state = jnp.asarray(seed)
    if trash:
        with jax.named_scope("engine.pad"):
            state = jnp.concatenate(
                [state, jnp.zeros((rho,) + seed.shape[1:], seed.dtype)],
                axis=0,
            )
    dtype = state.dtype
    _out_transform = out_block_transform(nb, trash)

    for sched in plan:
        fn, table = sched.map, sched.prefetch

        in_specs = [
            pl.BlockSpec(
                (rho,) * m,
                _make_index_map(
                    fn,
                    _out_transform if d == (0,) * m
                    else shift_block_transform(d, nb, boundary, trash),
                ),
            )
            for d in shifts
        ]
        in_specs += [
            body.extra_spec(a, x, m, nb, rho, fn)
            for a in range(len(extras))
        ]
        out_spec = pl.BlockSpec((rho,) * m, _make_index_map(fn, _out_transform))

        def _kernel_fn(*refs, fn=fn, table=table):
            if table is not None:
                pref = (refs[0],)
                refs = refs[1:]
            else:
                pref = ()
            halo_refs = refs[: len(shifts)]
            extra_refs = refs[len(shifts):-1]
            o_ref = refs[-1]
            ids = tuple(
                pl.program_id(i) for i in range(len(sched.grid))
            )
            out = fn(*ids, *pref)
            coords, valid = out[:-1], out[-1]
            blocks = tuple(coords[::-1])

            neighbors = None
            if body.halo:
                neighbors = _halo_tiles(
                    halo_refs, shifts, blocks, m, n, nb, rho, boundary,
                )
            centre = halo_refs[centre_idx][...]
            ctx = BodyContext(
                m=m, n=n, nb=nb, rho=rho, dtype=dtype,
                blocks=blocks, valid=valid, center=centre,
                neighbors=neighbors, extras=tuple(extra_refs),
            )
            o_ref[...] = jnp.where(
                ctx.mask(), body.tile(ctx), centre
            ).astype(o_ref.dtype)

        grid_spec, args = _grid_spec(table, sched.grid, in_specs, out_spec)
        alias_src = len(args) + centre_idx
        block_bytes = (len(shifts) + 1) * rho**m * dtype.itemsize + sum(
            rho * e.shape[1] * e.dtype.itemsize for e in extras
        )
        with jax.named_scope("engine.kernel"):
            state = pallas_launch(
                _kernel_fn,
                interpret=interpret,
                out_shape=jax.ShapeDtypeStruct(state.shape, dtype),
                grid_spec=grid_spec,
                input_output_aliases={alias_src: 0},
                name=_kernel_name(body.name, sched.kind),
                **_compiler_params(interpret, block_bytes, rho**m * 4),
            )(*args, *([state] * len(shifts)), *extras)
    if not trash:
        return state
    with jax.named_scope("engine.unpad"):
        return state[:n]


def _kernel_name(body: str, kind: str) -> str:
    """The launch's stable kernel name, ``engine_<body>_<kind>``."""
    return f"engine_{body}_{kind}".replace("-", "_")


def _compiler_params(interpret: bool, block_bytes: int,
                     tile_bytes: int) -> dict:
    """``compiler_params`` for a compiled launch (empty when interpreting).

    Args:
        interpret: Resolved launch mode.
        block_bytes: Bytes of one grid step's blocks, inputs and output.
        tile_bytes: Bytes of one 32-bit ``(rho,)*m`` temporary.

    Returns:
        Keyword arguments for ``pallas_launch``.
    """
    if interpret:
        return {}
    from jax.experimental.pallas import tpu as pltpu

    limit = vmem_limit_bytes(block_bytes, tile_bytes)
    if limit is None:
        return {}
    return {"compiler_params": pltpu.CompilerParams(vmem_limit_bytes=limit)}


def _halo_tiles(halo_refs, shifts, blocks, m, n, nb, rho, boundary):
    """The masked 3^m neighbour tiles of the current tile, by offset.

    Each shifted ref is masked by the domain predicate of ITS OWN
    position — wrapped coordinates under 'periodic' (matching the
    roll-of-masked-state reference semantics), true coordinates plus
    in-range checks under 'free' (clamp duplicates are inert).
    """
    tiles = {}
    shape = (rho,) * m
    for si, d in enumerate(shifts):
        t = halo_refs[si][...]
        if boundary == "periodic":
            tile_blocks = [(b + dj) % nb for b, dj in zip(blocks, d)]
            ok = domain_mask(m, n, _axis_coords(tile_blocks, rho, shape))
        else:
            tile_blocks = [b + dj for b, dj in zip(blocks, d)]
            g = _axis_coords(tile_blocks, rho, shape)
            ok = domain_mask(m, n, g)
            for gj in g:
                ok = ok & (gj >= 0) & (gj < n)
        tiles[d] = jnp.where(ok, t, 0)
    return tiles


def _window(tiles, d, rho, m):
    """Shifted view of a halo: element ``i`` reads position ``i + d``.

    Position ``i_j + d_j`` lies in the centre tile along axis j unless
    it steps over the edge, where it lies in the neighbour tile at
    block offset ``d_j``; within whichever tile it lies, its index is
    ``(i_j + d_j) mod rho`` — a rotate of that tile.  One candidate per
    combination of axes that step over, chosen by edge masks.
    """
    from jax.experimental.pallas import tpu as pltpu

    shape = (rho,) * m
    idx = [jax.lax.broadcasted_iota(jnp.int32, shape, j) for j in range(m)]
    over = [
        (idx[j] == rho - 1) if dj > 0 else (idx[j] == 0)
        for j, dj in enumerate(d) if dj
    ]
    moving = [j for j, dj in enumerate(d) if dj]
    out = None
    for picks in itertools.product((False, True), repeat=len(moving)):
        e = list((0,) * m)
        for j, pick in zip(moving, picks):
            if pick:
                e[j] = d[j]
        t = tiles[tuple(e)]
        for j in moving:
            t = pltpu.roll(t, (rho - d[j]) % rho, j)
        if out is None:
            out = t
            continue
        sel = None
        for o, pick in zip(over, picks):
            c = o if pick else jnp.logical_not(o)
            sel = c if sel is None else sel & c
        out = jnp.where(sel, t, out)
    return out


# ---------------------------------------------------------------------------
# bodies
# ---------------------------------------------------------------------------


class AccumBody(KernelBody):
    """ACCUM: +1 on every simplex element (the memory-bound test)."""

    name = "accum"
    element_local = True

    def tile(self, ctx: BodyContext):
        """One increment of the center tile."""
        return ctx.center + 1


class EDMBody(KernelBody):
    """EDM: sum of pairwise point distances per simplex cell.

    ``out[c] = sum_{a < b} ||p[c_a] - p[c_b]||`` over the cell's
    coordinates — at m=2 exactly the paper's Euclidean distance matrix
    ``||p_i - p_j||`` on the lower triangle; at m=3 the perimeter of
    the triangle ``(p_x, p_y, p_z)`` (arithmetic-heavy at every m).
    Out-of-domain elements are written 0 via the zeros seed.
    """

    name = "edm"
    element_local = True

    def seed(self, p, m: int):
        """Zeros seed: untouched tiles (and masked elements) read 0."""
        n, _ = p.shape
        return jnp.zeros((n,) * m, p.dtype), n

    def extra_arrays(self, p, m: int) -> tuple:
        """One (n, d) point-block operand per cell coordinate."""
        return (p,) * m

    def extra_spec(self, a, p, m, nb, rho, fn):
        """Fetch the ``(rho, d)`` point block of coordinate ``c_a``."""
        d = p.shape[1]

        def _transform(blocks, coords, v, a=a):
            return jnp.clip(coords[a], 0, nb - 1), 0

        return pl.BlockSpec((rho, d), _make_index_map(fn, _transform))

    def tile(self, ctx: BodyContext):
        """Accumulate ``||p_b - p_a||`` over coordinate pairs a < b."""
        m, rho = ctx.m, ctx.rho
        ps = [r[...].astype(jnp.float32) for r in ctx.extras]
        total = jnp.zeros((rho,) * m, jnp.float32)
        for a in range(m):
            for b in range(a + 1, m):
                # (i_b, i_a) orientation: axis m-1-b < axis m-1-a.
                d2 = jnp.sum(
                    (ps[b][:, None, :] - ps[a][None, :, :]) ** 2, axis=-1
                )
                dist = jnp.sqrt(d2)
                shape = [1] * m
                shape[m - 1 - b] = rho
                shape[m - 1 - a] = rho
                total = total + dist.reshape(shape)
        return total


class CABody(KernelBody):
    """CA: one Game-of-Life step (B3/S23 analogue, 3^m - 1 neighbours).

    m=2 wraps periodically on the underlying square (paper §5.1); m >= 3
    uses free boundaries (fixed dead cells outside the simplex).  Cells
    outside the domain are permanently dead; visited out-of-domain
    elements keep their input value (in-place semantics).
    """

    name = "ca"
    halo = True
    element_local = False

    def tile(self, ctx: BodyContext):
        """Masked centre + neighbour count from the halo windows."""
        m = ctx.m
        centre = ctx.window((0,) * m)
        neigh = jnp.zeros_like(centre)
        for d in itertools.product((-1, 0, 1), repeat=m):
            if d == (0,) * m:
                continue
            neigh = neigh + ctx.window(d)
        born = (centre == 0) & (neigh == 3)
        survive = (centre == 1) & ((neigh == 2) | (neigh == 3))
        return (born | survive).astype(ctx.dtype)


class MapBody(KernelBody):
    """MAP: materialize the schedule walk itself (the paper's
    theoretical-speedup microbenchmark).

    Output is a ``(steps, m+1)`` int32 table of ``(*coords, valid)``
    per grid step — CHUNK consecutive steps per launch step so the map
    cannot be elided (the CUDA version uses ``volatile`` for this).
    Overrides ``launch``: the output is a table, not a domain array.
    """

    name = "map"
    element_local = True

    def launch(self, kernel: "SimplexKernel", nb: int):
        """Chunked linear walk over the schedule's flattened grid."""
        m, chunk = kernel.m, kernel.chunk
        interpret = resolve_interpret(kernel.interpret)
        if kernel.schedule is not None:
            if kernel.schedule.m != m or kernel.schedule.n != nb:
                raise ValueError(
                    f"explicit schedule is (m={kernel.schedule.m}, "
                    f"nb={kernel.schedule.n}) but the launch needs "
                    f"(m={m}, nb={nb})"
                )
            sched = kernel.schedule
        else:
            sched = _schedule(m, nb, kernel.kind)
        fn, table = sched.map, sched.prefetch
        steps = sched.steps
        grid = sched.grid
        padded = ((steps + chunk - 1) // chunk) * chunk
        width = m + 1

        def _kernel_fn(*refs):
            if table is not None:
                tab_ref, o_ref = refs
                pref = (tab_ref,)
            else:
                (o_ref,) = refs
                pref = ()
            i = pl.program_id(0)
            lin = (
                i * chunk
                + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)[:, 0]
            )
            lin = jnp.minimum(lin, steps - 1)
            ws = []
            rem = lin
            for g in grid:
                ws.append(rem % g)
                rem = rem // g
            out = fn(*ws, *pref)
            coords, valid = out[:-1], out[-1]
            for j, c in enumerate(coords):
                o_ref[:, j] = c.astype(jnp.int32)
            o_ref[:, m] = valid.astype(jnp.int32)

        def _out_map(i, *pref):
            return i, 0

        grid_spec, args = _grid_spec(
            table, (padded // chunk,), [],
            pl.BlockSpec((chunk, width), _out_map),
        )
        with jax.named_scope("engine.kernel"):
            out = pallas_launch(
                _kernel_fn,
                interpret=interpret,
                out_shape=jax.ShapeDtypeStruct((padded, width), jnp.int32),
                grid_spec=grid_spec,
                name=_kernel_name(self.name, sched.kind),
            )(*args)
        with jax.named_scope("engine.unpad"):
            return out[:steps]

    def xla_executor(self, kernel: "SimplexKernel", nb: int):
        """The walk evaluated as ONE jit program (compiled.py)."""
        from .compiled import schedule_coords_compiled

        return schedule_coords_compiled(
            kernel.m, nb, resolve_kind(kernel.m, nb, kernel.kind)
        )


class _AccumXLA(AccumBody):
    """ACCUM with the fused-XLA executors wired in (the default body)."""

    def xla_executor(self, kernel: "SimplexKernel", x):
        """Route to ``accum2d_compiled`` / ``accum_md_compiled``."""
        from .compiled import accum2d_compiled, accum_md_compiled

        if kernel.m == 2:
            return accum2d_compiled(x, rho=kernel.rho, kind=kernel.kind)
        return accum_md_compiled(x, rho=kernel.rho, kind=kernel.kind)


register_body(_AccumXLA())
register_body(EDMBody())
register_body(CABody())
register_body(MapBody())


# ---------------------------------------------------------------------------
# the launcher
# ---------------------------------------------------------------------------


class SimplexKernel:
    """One launcher for every (body, dimension, schedule kind).

    ``SimplexKernel(body, m)`` resolves the body from the registry and
    launches it over any ``SimplexSchedule`` — the engine handles grid
    shape, scalar prefetch, trash-tile parking of invalid steps, halo assembly,
    execution policy, and composite launch splitting uniformly
    (DESIGN.md §2.3).

    Args:
        body: Registered body name ('map' | 'accum' | 'edm' | 'ca') or
            a ``KernelBody`` instance.
        m: Simplex dimension (m >= 2).
        rho: Tile side (default ``default_rho(m)``).
        kind: Schedule kind, ``'auto'`` for the autotuner.
        interpret: Pallas mode; None resolves per backend (policy.py).
        split: Force the composite per-piece launch split on/off; None
            asks ``repro.autotune.should_split_pieces``.
        chunk: MAP body only — steps materialized per launch step.
        executor: ``'pallas'`` (default) or ``'xla'`` — the fused-XLA
            fallback where the body provides one.
        schedule: An explicit schedule object (``.grid`` / ``.map`` /
            ``.prefetch`` surface, e.g. a ``ShardSchedule`` from
            ``distributed/simplex_sharding.py``) to launch instead of
            resolving ``kind``; must match the operand's (m, nb).
            The launch walks exactly its steps — the per-shard
            execution path of DESIGN.md §7.

    Example:
        >>> import numpy as np
        >>> k = SimplexKernel("accum", m=3, rho=2, kind="table")
        >>> x = np.zeros((4, 4, 4), np.int32)
        >>> int(np.asarray(k(x)).sum())  # V(T(4)) cells incremented
        20
    """

    def __init__(self, body, m: int, *, rho: Optional[int] = None,
                 kind: str = "auto", interpret: Optional[bool] = None,
                 split: Optional[bool] = None, chunk: int = 128,
                 executor: str = "pallas", schedule=None):
        if m < 2:
            raise ValueError(f"m must be >= 2, got {m}")
        if executor not in ("pallas", "xla"):
            raise ValueError(f"unknown executor {executor!r}")
        self.body = get_body(body)
        self.m = m
        self.rho = default_rho(m, interpret) if rho is None else rho
        self.kind = kind
        self.interpret = interpret
        self.split = split
        self.chunk = chunk
        self.executor = executor
        self.schedule = schedule

    def __call__(self, x):
        """Launch the body on operand ``x`` (domain array, points, or
        tile count for the MAP body)."""
        if self.executor == "xla":
            out = self.body.xla_executor(self, x)
            if out is None:
                raise NotImplementedError(
                    f"body {self.body.name!r} has no fused-XLA executor; "
                    "use executor='pallas' (interpret mode on CPU)"
                )
            return out
        return self.body.launch(self, x)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (
            f"SimplexKernel(body={self.body.name!r}, m={self.m}, "
            f"rho={self.rho}, kind={self.kind!r})"
        )


# ---------------------------------------------------------------------------
# functional entry points (what ops.py and new code call)
# ---------------------------------------------------------------------------


def map_table(nb: int, m: int = 2, kind: str = "hmap", chunk: int = 128,
              interpret: Optional[bool] = None,
              executor: str = "pallas") -> jax.Array:
    """The MAP test at any dimension: ``(steps, m+1)`` int32
    ``(*coords, valid)`` per grid step.

    Args:
        nb: Tile count per side.
        m: Simplex dimension.
        kind: Schedule kind.
        chunk: Steps per launch step.
        interpret: Pallas mode (None = policy).
        executor: 'pallas' or 'xla' (``schedule_coords_compiled``).

    Returns:
        The materialized schedule walk.
    """
    return SimplexKernel(
        "map", m, kind=kind, chunk=chunk, interpret=interpret,
        executor=executor,
    )(nb)


def accum(x: jax.Array, rho: Optional[int] = None, kind: str = "auto",
          interpret: Optional[bool] = None, split: Optional[bool] = None,
          executor: str = "pallas") -> jax.Array:
    """+1 on every simplex element of the m-cube ``x`` (m = x.ndim).

    Args:
        x: ``(n,)*m`` array, ``rho | n``; m=2 uses the inclusive
            lower-triangle domain, m >= 3 the strict simplex.
        rho: Tile side (default per dimension).
        kind: Schedule kind or 'auto'.
        interpret: Pallas mode (None = policy).
        split: Composite per-piece launch split (None = autotuned).
        executor: 'pallas' or 'xla' (fused-XLA executors).

    Returns:
        ``x`` with +1 on the domain; out-of-domain untouched.
    """
    return SimplexKernel(
        "accum", x.ndim, rho=rho, kind=kind, interpret=interpret,
        split=split, executor=executor,
    )(x)


def edm(p: jax.Array, m: int = 2, rho: Optional[int] = None,
        kind: str = "auto", interpret: Optional[bool] = None,
        split: Optional[bool] = None) -> jax.Array:
    """Pairwise-distance field over the m-simplex: the EDM test.

    ``out[c] = sum_{a<b} ||p[c_a] - p[c_b]||`` — the paper's Euclidean
    distance matrix at m=2, its dimension-generic sibling beyond.

    Args:
        p: ``(n, d)`` points.
        m: Simplex dimension of the output field.
        rho: Tile side (default per dimension).
        kind: Schedule kind or 'auto'.
        interpret: Pallas mode (None = policy).
        split: Composite per-piece launch split (None = autotuned).

    Returns:
        ``(n,)*m`` array in ``p.dtype``; 0 outside the domain.
    """
    return SimplexKernel(
        "edm", m, rho=rho, kind=kind, interpret=interpret, split=split,
    )(p)


def ca(state: jax.Array, rho: Optional[int] = None, kind: str = "auto",
       interpret: Optional[bool] = None) -> jax.Array:
    """One Game-of-Life step on the m-simplex (m = state.ndim).

    Args:
        state: ``(n,)*m`` 0/1 array.
        rho: Tile side (default per dimension).
        kind: Schedule kind or 'auto'.
        interpret: Pallas mode (None = policy).

    Returns:
        The stepped state; out-of-domain elements untouched.
    """
    return SimplexKernel(
        "ca", state.ndim, rho=rho, kind=kind, interpret=interpret,
    )(state)


def edm2d(p: jax.Array, rho: Optional[int] = None, kind: str = "auto",
          interpret: Optional[bool] = None) -> jax.Array:
    """The m=2 EDM body — ``out[i, j] = ||p_i - p_j||`` on the
    inclusive lower triangle (engine-built; see ``edm``)."""
    return edm(p, 2, rho=rho, kind=kind, interpret=interpret)


def edm3d(p: jax.Array, rho: Optional[int] = None, kind: str = "auto",
          interpret: Optional[bool] = None,
          split: Optional[bool] = None) -> jax.Array:
    """The m=3 EDM body: per-cell triangle perimeter
    ``||p_x-p_y|| + ||p_x-p_z|| + ||p_y-p_z||`` on T(n) (see ``edm``)."""
    return edm(p, 3, rho=rho, kind=kind, interpret=interpret, split=split)


def edm_md(p: jax.Array, m: int, rho: Optional[int] = None,
           kind: str = "auto", interpret: Optional[bool] = None,
           split: Optional[bool] = None) -> jax.Array:
    """The general-m EDM body (m >= 3; ``edm2d`` serves the triangle).

    Args:
        p: ``(n, d)`` points.
        m: Simplex dimension, m >= 3.
        rho: Tile side (default per dimension).
        kind: Schedule kind or 'auto'.
        interpret: Pallas mode (None = policy).
        split: Composite per-piece launch split (None = autotuned).

    Returns:
        ``(n,)*m`` pairwise-distance field; 0 outside T(n).
    """
    if m < 3:
        raise ValueError("edm_md serves m >= 3; use edm2d for the triangle")
    return edm(p, m, rho=rho, kind=kind, interpret=interpret, split=split)


def ca_md(state: jax.Array, rho: Optional[int] = None, kind: str = "auto",
          interpret: Optional[bool] = None) -> jax.Array:
    """The general-m CA body: (3^m - 1)-neighbour Game of Life on T(n),
    free boundaries (m = state.ndim >= 3; ``ca`` at m=2 wraps).

    Args:
        state: ``(n,)*m`` 0/1 array, m >= 3.
        rho: Tile side (default per dimension).
        kind: Schedule kind or 'auto'.
        interpret: Pallas mode (None = policy).

    Returns:
        The stepped state; out-of-domain elements untouched.
    """
    if state.ndim < 3:
        raise ValueError("ca_md serves m >= 3; use ca for the 2-simplex")
    return ca(state, rho=rho, kind=kind, interpret=interpret)


def accum_md(x: jax.Array, rho: Optional[int] = None, kind: str = "auto",
             interpret: Optional[bool] = None,
             split: Optional[bool] = None) -> jax.Array:
    """The general-m ACCUM body (m = x.ndim >= 3; see ``accum``)."""
    if x.ndim < 3:
        raise ValueError("accum_md serves m >= 3; use accum at m=2")
    return accum(x, rho=rho, kind=kind, interpret=interpret, split=split)


def grid_steps(nb: int, kind: str, m: int = 2) -> int:
    """Grid steps the engine would launch for ``(m, nb, kind)`` after
    kernel-facing kind resolution.

    Args:
        nb: Tile count per side.
        kind: Requested schedule kind.
        m: Simplex dimension.

    Returns:
        Total grid steps of the resolved schedule.
    """
    return _schedule(m, nb, kind).steps
