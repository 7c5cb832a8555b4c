"""Halo-conformance pass: declared stencils vs blocks actually touched.

A ``KernelBody`` *declares* the 3^m block-offset stencil its per-tile
compute reads (``KernelBody.stencil`` — full neighborhood for ``halo``
bodies, centre-only otherwise); the engine *fetches* one shifted input
ref per offset in ``kernels.engine.launch_shifts`` and builds each
ref's ``BlockSpec`` index map from
``kernels.engine.shift_block_transform``.  This pass diffs the two and
then replays every fetch map over real schedule walks (DESIGN.md §9):

* an offset the engine fetches but the body does not declare is an
  **undeclared halo read** — the compute can observe blocks the
  contract says it never touches;
* a declared offset the engine never fetches is a **stale declaration**
  — the compute would read unassembled (zero) neighbours;
* for every fetched offset, the evaluated index map must equal the
  boundary-correct neighbour (wrap mod nb under ``'periodic'``, clip +
  trash-park under ``'free'``) and stay inside ``[0, nb]`` — the range
  Pallas can actually address after trash-tile padding.  A walk the
  engine launches without the trash row (``core.schedule.
  every_step_valid``) must have no invalid step and stay inside
  ``[0, nb-1]``.

All checks replay index maps with numpy step enumerations — no Pallas
launch.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from .registry import Finding, LintContext, register_pass
from .schedule_passes import eval_schedule_map

__all__ = [
    "HALO_MN",
    "check_body_halo",
]

# (m, nb, kind) combos the registered pass replays per body: a pow2
# multi-axis walk, the bounding-box walk (invalid steps exercise the
# trash parking), a non-pow2 composite walk at m=3, and an exact walk
# at m=3 (free boundary, no trash row).
HALO_MN: Tuple[Tuple[int, int, str], ...] = (
    (2, 4, "hmap"),
    (2, 4, "bb"),
    (3, 4, "hmap"),
    (3, 4, "bb"),
    (3, 3, "composite"),
    (3, 4, "table"),
)


def check_body_halo(body, m: int, nb: int, kind: str) -> List[Finding]:
    """Verify one body's stencil declaration at one (m, nb, kind).

    Args:
        body: A ``KernelBody`` instance (or registered name).
        m: Simplex dimension.
        nb: Tile count per side.
        kind: Schedule kind to replay the fetch maps over.

    Returns:
        Findings for declaration/fetch mismatches, boundary-handling
        drift, and out-of-range fetches; empty when conformant.
    """
    from repro.core.schedule import (
        SimplexSchedule,
        every_step_valid,
        resolve_kind,
    )
    from repro.kernels.engine import (
        get_body,
        launch_shifts,
        shift_block_transform,
    )

    body = get_body(body)
    where = (
        f"<semantic:body {body.name} m={m} nb={nb} kind={kind}>"
    )
    declared = set(body.stencil(m))
    fetched = set(launch_shifts(body, m))
    out: List[Finding] = []
    for d in sorted(fetched - declared):
        out.append(Finding(
            "halo-conformance", where, 0,
            f"undeclared halo read: engine fetches block offset {d} "
            f"but {body.name}.stencil({m}) does not declare it",
        ))
    for d in sorted(declared - fetched):
        out.append(Finding(
            "halo-conformance", where, 0,
            f"stale stencil declaration: {body.name}.stencil({m}) "
            f"declares offset {d} the engine never fetches (the "
            "compute would read unassembled zeros)",
        ))
    if out:
        return out

    sched = SimplexSchedule(m, nb, resolve_kind(m, nb, kind))
    coords, valid = eval_schedule_map(sched)
    blocks = tuple(c for c in coords[::-1])  # array-axis order
    boundary = body.boundary(m)
    trash = not every_step_valid(sched)
    if not trash and not valid.all():
        return [Finding(
            "halo-conformance", where, 0,
            f"grid step {int(np.nonzero(~valid)[0][0])} is invalid, but "
            "the walk is launched without a trash row (every_step_valid)",
        )]
    top = nb if trash else nb - 1  # the last block row Pallas can address
    for d in sorted(fetched):
        tr = shift_block_transform(d, nb, boundary, trash)
        got = [
            np.asarray(b).astype(np.int64)
            for b in tr(blocks, coords, valid)
        ]
        if boundary == "periodic":
            want = [
                (blocks[j] + d[j]) % nb for j in range(m)
            ]
        else:
            want = [
                np.clip(blocks[j] + d[j], 0, nb - 1) for j in range(m)
            ]
            if trash:
                want[0] = np.where(valid, want[0], nb)
        for j in range(m):
            bad = np.nonzero(got[j] != want[j])[0]
            if bad.size:
                s = int(bad[0])
                out.append(Finding(
                    "halo-conformance", where, 0,
                    f"fetch map for offset {d} touches block "
                    f"{tuple(int(g[s]) for g in got)} at grid step {s}; "
                    f"the {boundary} boundary rule expects "
                    f"{tuple(int(w[s]) for w in want)}",
                ))
                break
        lo_ok = all((g >= 0).all() for g in got)
        hi_ok = (got[0] <= top).all() and all(
            (g <= nb - 1).all() for g in got[1:]
        )
        if not (lo_ok and hi_ok):
            out.append(Finding(
                "halo-conformance", where, 0,
                f"fetch map for offset {d} addresses a block outside "
                f"[0, {top}] — unmapped memory for this launch",
            ))
    return out


def _domain_bodies():
    """Registered bodies launched through the generic domain launcher
    (bodies overriding ``launch`` — MAP — have no block stencil)."""
    from repro.kernels.engine import KernelBody, get_body, registered_bodies

    for name in registered_bodies():
        body = get_body(name)
        if type(body).launch is KernelBody.launch:
            yield body


@register_pass(
    "halo-conformance", "semantic",
    "each body's declared stencil matches the blocks its index maps "
    "touch",
)
def _halo_pass(ctx: LintContext,
               combos: Optional[Sequence] = None) -> List[Finding]:
    out: List[Finding] = []
    for body in _domain_bodies():
        for m, nb, kind in (combos or HALO_MN):
            out.extend(check_body_halo(body, m, nb, kind))
    return out
