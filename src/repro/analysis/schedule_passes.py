"""Semantic schedule passes: race + bijectivity, no Pallas launch.

The correctness story of the block-space map H (PAPER.md §4) reduces to
two schedule-level facts the engine otherwise only observes at runtime:

* **bijectivity** — the valid steps of a walk cover the blocked simplex
  exactly once each (no hole, no duplicate) with every coordinate in
  range; and
* **write-race freedom** — after the engine's output transform (clip,
  plus trash-tile parking where the walk can hold an invalid step,
  ``kernels.engine.out_block_transform``) no two grid steps write the
  same live output block, and every invalid step parks at the trash
  row; a walk launched without one has no invalid step.

Both are decidable by replaying ``SimplexSchedule.map`` over the full
step enumeration (``core.schedule.step_grid_indices``) on small (m, n)
grids — numpy arrays in, no kernel launch.  The registered passes run
the ``DEFAULT_MN`` matrix over every registered kind (kernel-facing
resolution included, so non-pow2 requests verify the ``composite``
walk they actually launch) plus the k-way ``shard`` views of
``distributed.simplex_sharding`` (DESIGN.md §7, §9).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .registry import Finding, LintContext, register_pass

__all__ = [
    "DEFAULT_MN",
    "SHARD_COUNTS",
    "eval_schedule_map",
    "check_schedule_bijectivity",
    "check_schedule_race",
    "verified_schedules",
]

# (pow2 n, non-pow2 n) verified per dimension — every registered kind
# at every m is checked at both, through kernel-facing kind resolution.
DEFAULT_MN: Dict[int, Tuple[int, int]] = {2: (8, 6), 3: (8, 6), 4: (4, 6)}

# k values for the shard-view verification at each (m, n).
SHARD_COUNTS: Tuple[int, ...] = (2, 3)


def eval_schedule_map(sched) -> Tuple[List[np.ndarray], np.ndarray]:
    """Replay a schedule's map over its full step enumeration.

    Args:
        sched: Any schedule surface (``.grid``/``.steps``/``.map``/
            ``.prefetch``) — ``SimplexSchedule``, piece, or shard.

    Returns:
        ``(coords, valid)``: m math-order int coordinate arrays and the
        boolean validity flag, one entry per grid step.

    Example:
        >>> from repro.core.schedule import SimplexSchedule
        >>> coords, valid = eval_schedule_map(SimplexSchedule(2, 4, "bb"))
        >>> int(valid.sum())  # tri(4) valid steps in the 4x4 box
        10
    """
    from repro.core.schedule import step_grid_indices

    ws = step_grid_indices(sched)
    pref = getattr(sched, "prefetch", None)
    out = sched.map(*ws, *(() if pref is None else (pref,)))
    coords = [np.asarray(c).astype(np.int64) for c in out[:-1]]
    valid = np.asarray(out[-1]).astype(bool)
    return coords, valid


def _domain_set(m: int, n: int) -> set:
    """All in-domain blocks: m=2 inclusive lower triangle, else sum<n."""
    if m == 2:
        return {(x, y) for y in range(n) for x in range(y + 1)}
    from repro.core.simplex import enumerate_simplex

    return set(map(tuple, enumerate_simplex(n, m)))


def _label(sched, m: int, n: int) -> str:
    kind = getattr(sched, "kind", "?")
    return f"<semantic:schedule m={m} n={n} kind={kind}>"


def check_schedule_bijectivity(sched, m: int, n: int,
                               pass_name: str = "schedule-bijectivity",
                               ) -> List[Finding]:
    """Valid steps must hit every domain block exactly once, in range.

    Args:
        sched: The schedule (or shard/piece view) to verify.
        m: Simplex dimension.
        n: Blocked side length the walk covers.
        pass_name: Name stamped on the findings.

    Returns:
        Findings for out-of-bounds coordinates, out-of-domain valid
        steps, duplicate coverage, and uncovered domain blocks.
    """
    coords, valid = eval_schedule_map(sched)
    where = _label(sched, m, n)
    out: List[Finding] = []
    stack = np.stack(coords, axis=1)  # (steps, m), math order
    vstack = stack[valid]
    oob = (vstack < 0) | (vstack >= n)
    if oob.any():
        step = int(np.nonzero(oob.any(axis=1))[0][0])
        out.append(Finding(
            pass_name, where, 0,
            f"out-of-bounds coordinate {tuple(vstack[step])} on a valid "
            f"step (n={n})",
        ))
        return out
    domain = _domain_set(m, n)
    seen: Dict[tuple, int] = {}
    for row in map(tuple, vstack):
        seen[row] = seen.get(row, 0) + 1
    for row, count in seen.items():
        if row not in domain:
            out.append(Finding(
                pass_name, where, 0,
                f"valid step maps outside the simplex domain: {row}",
            ))
        elif count > 1:
            out.append(Finding(
                pass_name, where, 0,
                f"block {row} covered {count} times (walk is not "
                "injective on valid steps)",
            ))
    missing = domain - set(seen)
    if missing:
        out.append(Finding(
            pass_name, where, 0,
            f"{len(missing)} domain blocks never visited, e.g. "
            f"{sorted(missing)[:3]}",
        ))
    return out


def check_schedule_race(sched, m: int, n: int,
                        pass_name: str = "write-race") -> List[Finding]:
    """No two grid steps may write the same live output block.

    Applies the engine's actual output index-map transform
    (``kernels.engine.out_block_transform``: clip to range, and park
    invalid steps at the trash row where the launch has one — that is,
    unless ``core.schedule.every_step_valid``) to every step of the
    walk, then checks (a) valid steps land on pairwise-distinct blocks —
    two steps sharing an output block is the λ-map overlap race, the
    launch-order-dependent write the triangular-map line of work guards
    against — and (b) invalid steps all park at the trash row, never on
    a live block.  A walk launched without the trash row must have no
    invalid step and every block index in ``[0, n-1]``.

    Args:
        sched: The schedule (or shard/piece view) to verify.
        m: Simplex dimension.
        n: Blocked side length (trash row index).
        pass_name: Name stamped on the findings.

    Returns:
        Findings for racing step pairs, mis-parked invalid steps, and
        invalid or out-of-range steps of a walk with no trash row.
    """
    from repro.core.schedule import every_step_valid
    from repro.kernels.engine import out_block_transform

    coords, valid = eval_schedule_map(sched)
    where = _label(sched, m, n)
    blocks = tuple(coords[::-1])  # array-axis order
    trash = not every_step_valid(sched)
    out_blocks = out_block_transform(n, trash)(blocks, coords, valid)
    cols = [np.asarray(b).astype(np.int64) for b in out_blocks]
    stack = np.stack(cols, axis=1)  # (steps, m)
    out: List[Finding] = []
    if not trash:
        bad = np.nonzero(~valid)[0]
        if bad.size:
            out.append(Finding(
                pass_name, where, 0,
                f"grid step {int(bad[0])} is invalid, but the walk is "
                "launched without a trash row (every_step_valid) and "
                "writes a live block",
            ))
        off = np.nonzero(((stack < 0) | (stack > n - 1)).any(axis=1))[0]
        if off.size:
            out.append(Finding(
                pass_name, where, 0,
                f"grid step {int(off[0])} writes block "
                f"{tuple(stack[off[0]])} outside [0, {n - 1}], and the "
                "launch has no trash row",
            ))
    seen: Dict[tuple, int] = {}
    for step, row in enumerate(map(tuple, stack)):
        if valid[step]:
            if row in seen:
                out.append(Finding(
                    pass_name, where, 0,
                    f"write race: grid steps {seen[row]} and {step} both "
                    f"write output block {row}",
                ))
            else:
                seen[row] = step
        elif trash and row[0] != n:
            out.append(Finding(
                pass_name, where, 0,
                f"invalid grid step {step} writes live block {row} "
                f"instead of parking at the trash row {n}",
            ))
    return out


def verified_schedules(m: int, n: int):
    """The schedule views the semantic passes verify at one (m, n).

    Yields every registered kind after kernel-facing resolution
    (``resolve_kind`` — what a launch at this (m, n) actually walks),
    the per-piece views of composite walks, and the k-way
    ``ShardSchedule`` views of the fold partition for each k in
    ``SHARD_COUNTS``.

    Args:
        m: Simplex dimension.
        n: Blocked side length.

    Yields:
        ``(label, views)`` pairs — ``views`` is a list of schedule
        objects whose *union* of valid steps must cover the domain
        bijectively (a single schedule for plain kinds).
    """
    from repro.core.schedule import (
        SimplexSchedule,
        registered_kinds,
        resolve_kind,
    )

    resolved_seen = set()
    for kind in registered_kinds(m):
        resolved = resolve_kind(m, n, kind)
        if resolved in resolved_seen:
            continue
        resolved_seen.add(resolved)
        try:
            sched = SimplexSchedule(m, n, resolved)
        except (ValueError, AssertionError):
            continue
        yield f"{kind}->{resolved}" if resolved != kind else kind, [sched]
        if resolved == "composite":
            yield "composite-pieces", list(sched.split_pieces())

    from repro.distributed.simplex_sharding import shard_schedules

    base = SimplexSchedule(m, n, "table")
    for k in SHARD_COUNTS:
        yield f"shard(k={k})", list(shard_schedules(base, k))


def _union_findings(check, views, m, n) -> List[Finding]:
    """Run ``check`` on the union of several schedule views.

    Single view: delegate.  Multiple views (shards, pieces): each view
    is checked for internal consistency *and* the union must cover the
    domain exactly once — a cross-view duplicate is a race/coverage
    violation even when every view is clean in isolation.
    """
    if len(views) == 1:
        return check(views[0], m, n)
    out: List[Finding] = []
    union = _UnionSchedule(views)
    out.extend(check(union, m, n))
    return out


class _UnionSchedule:
    """Concatenated view of several schedules (shards/pieces) so the
    union walk can be verified with the single-schedule checkers."""

    def __init__(self, views):
        from repro.core.schedule import every_step_valid

        self.views = views
        # one trash row or none for a split plan's pieces; shards share
        # their base walk's answer
        self.exact = all(every_step_valid(v) for v in views)
        self.kind = "+".join(
            str(getattr(v, "kind", "?")) for v in views[:1]
        ) + f"[x{len(views)}]"
        self.m = views[0].m
        self.n = views[0].n
        self.prefetch = None
        self.steps = sum(v.steps for v in views)
        self.grid = (self.steps,)

    def map(self, lin):
        """Concatenated evaluation (host-side verification only)."""
        lin = np.asarray(lin)
        coords_cols = None
        valids = []
        chunks = []
        off = 0
        for v in self.views:
            pref = getattr(v, "prefetch", None)
            ws = []
            sub = np.arange(v.steps, dtype=np.int64)
            rem = sub
            for g in v.grid:
                ws.append(rem % g)
                rem = rem // g
            out = v.map(*ws, *(() if pref is None else (pref,)))
            chunks.append([np.asarray(c) for c in out[:-1]])
            valids.append(np.asarray(out[-1]).astype(bool))
            off += v.steps
        m = len(chunks[0])
        coords_cols = [
            np.concatenate([c[j] for c in chunks]) for j in range(m)
        ]
        valid = np.concatenate(valids)
        return tuple(coords_cols) + (valid,)


def _run_matrix(check, pass_name: str,
                mn: Optional[Dict[int, Sequence[int]]] = None,
                ) -> List[Finding]:
    out: List[Finding] = []
    for m, ns in (mn or DEFAULT_MN).items():
        for n in ns:
            for label, views in verified_schedules(m, n):
                out.extend(_union_findings(check, views, m, n))
    return out


@register_pass(
    "schedule-bijectivity", "semantic",
    "every registered kind's valid steps cover the simplex exactly once",
)
def _bijectivity_pass(ctx: LintContext) -> List[Finding]:
    return _run_matrix(check_schedule_bijectivity, "schedule-bijectivity")


@register_pass(
    "write-race", "semantic",
    "no two grid steps write the same live output block (engine "
    "out-transform applied)",
)
def _race_pass(ctx: LintContext) -> List[Finding]:
    return _run_matrix(check_schedule_race, "write-race")
