"""Roofline analysis from compiled dry-run artifacts.

Three terms (per-chip seconds), at the peaks of the record's chip
(``PEAKS``, keyed by ``device_kind``; TPU v5e: 197e12 bf16 FLOP/s,
819e9 B/s HBM, 50e9 B/s per ICI link):
  compute    = HLO_FLOPs / (chips * peak FLOP/s)
  memory     = HLO_bytes / (chips * HBM B/s)
  collective = wire_bytes_per_chip / link B/s

Collective bytes are NOT in cost_analysis(); they are parsed from the
compiled HLO text: for every all-reduce / all-gather / reduce-scatter /
all-to-all / collective-permute we read operand & result shapes and the
replica-group size g, then apply ring-transfer formulas for per-chip
wire traffic:
    all-gather      result_bytes * (g-1)/g
    reduce-scatter  operand_bytes * (g-1)/g
    all-reduce      operand_bytes * 2(g-1)/g
    all-to-all      operand_bytes * (g-1)/g
    collective-perm operand_bytes
(cost_analysis FLOPs/bytes are *global* across the mesh; wire bytes here
are per chip already, so the collective term divides by one link's
bandwidth.)

MODEL_FLOPS = 6 * N_active * tokens (the usual dense-training estimate;
fwd-only modes use 2 * N_active * tokens); the ratio MODEL_FLOPS /
HLO_FLOPs shows how much compiled compute is "useful" — remat recompute
and schedule waste push it down.
"""

from __future__ import annotations

import json
import os
import re
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np


@dataclass(frozen=True)
class DevicePeaks:
    """Published peaks of one chip.

    Attributes:
        flops: bf16 FLOP/s.
        hbm_bw: HBM bytes/s.
        link_bw: Bytes/s of one chip-to-chip (ICI) link.
    """

    flops: float
    hbm_bw: float
    link_bw: float


# Keyed by ``jax.Device.device_kind``.  Source: Google Cloud
# documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s,
# 1,600 Gbit/s of interconnect over four 50 GB/s links).
PEAKS: Dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(flops=197e12, hbm_bw=819e9, link_bw=50e9),
}
# The chip the cost model ranks for in a process with no TPU attached
# (the CPU runs kernels only in interpret mode), and the chip the
# dry-run records describe.
MODEL_DEVICE_KIND = "TPU v5 lite"

# -- schedule cost-model constants (per grid step / per launch) -------------
# Scalar-unit costs of the index_map forms on the compiled path; coarse,
# but their *ratios* are what the autotuner ranks kinds by (DESIGN.md §5).
SELECT_S = 1.5e-9  # one branchless select/compare chain element
SMEM_READ_S = 4e-9  # one scalar-prefetch (SMEM) coordinate read
PREDICATE_S = 1.0e-9  # the bb add-compare validity predicate
LAUNCH_OVERHEAD_S = 5e-6  # fixed cost of one extra pallas_call launch
HOST_ENUM_S = 2.5e-8  # host-side per-cell cost of an O(V) table build
TABLE_AMORTIZE = 1000  # launches a built table is amortized over
# Attention entries (DESIGN.md §8): per-block-pair scalar overheads of
# the three causal-attention executors choose_attn_impl ranks.
ATTN_FOLD_SELECT_S = 2 * SELECT_S  # the _folded_qkv where/compare pair
ATTN_GATHER_S = SMEM_READ_S  # chunked-XLA per-step tile gather/scatter
# Per-grid-step cost of the Pallas *interpreter* (emulated index_maps +
# per-block dispatch) — the term that sends huge grids to the chunked
# XLA path on interpret-only backends.
INTERPRET_STEP_S = 2e-5

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUP_RE = re.compile(r"replica_groups=\{([^}]*)\}")
_GROUP_V2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")

_COLL_OPS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)

__all__ = [
    "DevicePeaks",
    "PEAKS",
    "MODEL_DEVICE_KIND",
    "device_peaks",
    "backend_peaks",
    "collective_census",
    "roofline_terms",
    "load_cells",
    "wire_bytes",
    "schedule_cost_model",
]


def device_peaks(device_kind: str) -> DevicePeaks:
    """The peaks of one chip kind.

    Args:
        device_kind: ``jax.Device.device_kind``, e.g. ``'TPU v5 lite'``.

    Returns:
        Its ``PEAKS`` entry.

    Raises:
        ValueError: The kind has no entry — no default stands in.
    """
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks recorded for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None


def backend_peaks() -> DevicePeaks:
    """The peaks a cost model in this process ranks against.

    With a TPU attached, its own entry (an unknown kind raises).  A
    process with no chip runs no chip kernel to measure, so its
    rankings model ``MODEL_DEVICE_KIND``.

    Returns:
        The ``DevicePeaks`` to model with.
    """
    import jax

    if jax.default_backend() == "tpu":
        return device_peaks(jax.devices()[0].device_kind)
    return device_peaks(MODEL_DEVICE_KIND)


def schedule_cost_model(
    kind: str,
    steps: int,
    *,
    m: int,
    n: int,
    useful: int,
    pieces: int = 1,
    rho: int = 8,
    dtype_bytes: int = 4,
    head_dim: int = 0,
    peaks: Optional[DevicePeaks] = None,
) -> float:
    """Predicted seconds per launch of one schedule kind (memory-bound).

    The model the ``repro.autotune`` tuner ranks candidate kinds with
    when no measured ``BENCH_maps.json`` row applies.  Two terms:

    * tile traffic — each grid step streams one (rho,)*m tile in and out
      of HBM: ``steps * 2 * rho^m * dtype_bytes / hbm_bw``.  Wasted
      steps (steps > useful) pay full traffic, which is exactly how the
      paper's extra parallel space costs on hardware.
    * index-map overhead — per-step scalar work of the map form:
      ``bb`` one predicate; ``table`` one SMEM read (plus its O(V) host
      build amortized over ``TABLE_AMORTIZE`` launches); ``hmap``/
      ``octant`` a log2(n)-level select chain; ``composite`` an
      O(pieces) select chain (the term the per-piece launch split
      removes — see ``repro.autotune.should_split_pieces``).

    Attention entries (DESIGN.md §8): kinds ``attn-folded`` /
    ``attn-bb`` / ``attn-chunked`` model the causal-attention hot path
    on the 2-simplex tile grid (``steps`` = block-pair visits, ``rho``
    = the square score-tile side, ``head_dim`` = D).  Each step moves
    three ``rho x head_dim`` operand tiles plus the output tile and
    pays two ``rho x rho x head_dim`` MXU matmuls; the per-step scalar
    overhead is the fold select chain (``attn-folded``), the causal
    predicate on every bounding-box step (``attn-bb``), or the XLA
    tile gather/scatter (``attn-chunked``).  This is the analytic
    prior ``repro.autotune.choose_attn_impl`` ranks executors with
    before measured ATTN rows exist.

    Args:
        kind: Registered schedule kind, or an ``attn-*`` entry.
        steps: Grid steps the schedule launches.
        m: Simplex dimension.
        n: Tile count per side.
        useful: Simplex cells covered (V) — table build cost scales on it.
        pieces: Composite piece count (ignored for other kinds).
        rho: Tile side in elements.
        dtype_bytes: Element width.
        head_dim: Attention head dim (``attn-*`` kinds only).
        peaks: Chip to model; None = ``backend_peaks()``.

    Returns:
        Predicted seconds for one launch of the full walk.
    """
    if peaks is None:
        peaks = backend_peaks()
    hbm_bw = peaks.hbm_bw
    if kind.startswith("attn-"):
        d = head_dim or rho
        tile_bytes = (3 * rho * d + rho * d) * dtype_bytes  # q,k,v in + o out
        if kind == "attn-chunked":
            # the XLA realization round-trips the (rho, rho) score tile
            # through HBM between HLO ops; the Pallas kernel keeps it
            # in VMEM — the structural reason flash wins on device.
            tile_bytes += 2 * rho * rho * dtype_bytes
        t_mem = steps * tile_bytes / hbm_bw
        t_mxu = steps * 2 * (2 * rho * rho * d) / peaks.flops
        per_step = {
            "attn-folded": ATTN_FOLD_SELECT_S,
            "attn-bb": PREDICATE_S,
            "attn-chunked": ATTN_GATHER_S,
        }.get(kind)
        if per_step is None:
            raise ValueError(f"unknown attention cost-model kind {kind!r}")
        return t_mem + t_mxu + steps * per_step
    tile_bytes = 2 * (rho**m) * dtype_bytes  # read + write
    t_mem = steps * tile_bytes / hbm_bw
    if kind == "bb":
        per_step = PREDICATE_S
        build = 0.0
    elif kind == "table":
        per_step = SMEM_READ_S
        build = useful * HOST_ENUM_S / TABLE_AMORTIZE
    elif kind == "composite":
        per_step = SELECT_S * max(pieces, 1)
        build = 0.0
    else:  # hmap / octant / rb: select chain over the recursion levels
        levels = max(int(n - 1).bit_length(), 1)
        per_step = SELECT_S * levels
        build = 0.0
    return t_mem + steps * per_step + build


def _shape_bytes(tok: str) -> int:
    m = _SHAPE_RE.match(tok)
    if not m:
        return 0
    dt, dims = m.groups()
    if dt not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def _group_size(line: str, default: int = 2) -> int:
    m = _GROUP_V2_RE.search(line)
    if m:  # iota tile form [num_groups, group_size]
        return max(int(m.group(2)), 1)
    m = _GROUP_RE.search(line)
    if m:
        first = m.group(1).split("}")[0].strip("{} ")
        if first:
            return max(len(first.split(",")), 1)
    return default


def wire_bytes(kind: str, operand: int, result: int, g: int) -> float:
    if g <= 1:
        return 0.0
    if kind == "all-gather":
        return result * (g - 1) / g
    if kind == "reduce-scatter":
        return operand * (g - 1) / g
    if kind == "all-reduce":
        return operand * 2 * (g - 1) / g
    if kind == "all-to-all":
        return operand * (g - 1) / g
    if kind == "collective-permute":
        return operand
    return 0.0


def collective_census(hlo_text: str) -> Dict:
    """Parse the compiled HLO; returns per-op-kind counts/bytes and the
    per-chip wire-byte total.  Robust to both replica_groups syntaxes."""
    per_kind: Dict[str, Dict[str, float]] = {}
    total_wire = 0.0
    for line in hlo_text.splitlines():
        ls = line.strip()
        if not ls or "=" not in ls:
            continue
        m = re.search(r"=\s*(\w+\[[^\]]*\][^ ]*)\s+([a-z0-9-]+)\(", ls)
        if not m:
            continue
        kind = m.group(2)
        # strip -start/-done fusion suffixes (async collectives)
        base = kind.replace("-start", "").replace("-done", "")
        if base not in _COLL_OPS:
            continue
        if kind.endswith("-done"):
            continue  # counted at -start
        result_b = _shape_bytes(m.group(1))
        # operand shapes: inside the call parens
        inner = ls[m.end(2) + 1 :]
        operand_b = sum(
            _shape_bytes(t) for t in re.findall(r"\w+\[[\d,]*\]", inner)
        )
        if operand_b == 0:
            operand_b = result_b
        g = _group_size(ls)
        wb = wire_bytes(base, operand_b, result_b, g)
        k = per_kind.setdefault(
            base, {"count": 0, "operand_bytes": 0.0, "wire_bytes": 0.0}
        )
        k["count"] += 1
        k["operand_bytes"] += operand_b
        k["wire_bytes"] += wb
        total_wire += wb
    return {"per_kind": per_kind, "wire_bytes_per_chip": total_wire}


def roofline_terms(rec: Dict) -> Dict:
    """rec: one dry-run cell JSON record (see launch/dryrun.py).

    The memory term is a BAND: ``memory_floor_s`` is the analytic
    minimum HBM traffic (each chip streams its model-parallel slice of
    the weights once per pass: microbatches x 3 passes for train with
    full remat, 1 pass for prefill/decode — the classic weights-bound
    floor); ``memory_s`` is the loop-aware HLO-granularity upper bound
    (CPU-backend fusion is coarser than TPU's, so real traffic sits in
    between).  Dominance uses the conservative floor.
    """
    chips = rec["n_chips"]
    peaks = device_peaks(rec.get("device_kind", MODEL_DEVICE_KIND))
    t_compute = rec["flops"] / (chips * peaks.flops)
    t_memory_hi = rec["bytes_accessed"] / (chips * peaks.hbm_bw)
    model_size = rec.get("model_axis", 16)
    passes = (3 * rec.get("microbatches", 1)) if rec["mode"] == "train" else 1
    param_bytes = rec["params"] * 4.0  # f32 master storage
    floor = passes * param_bytes / model_size / peaks.hbm_bw
    t_coll = rec["collectives"]["wire_bytes_per_chip"] / peaks.link_bw
    terms = {
        "compute_s": t_compute,
        "memory_floor_s": floor,
        "collective_s": t_coll,
    }
    dom = max(terms, key=terms.get)
    factor = 6 if rec["mode"] == "train" else 2
    model_flops = factor * rec["params_active"] * rec["tokens"]
    hlo = max(rec["flops"], 1.0)
    bound = max(terms.values())
    ideal = model_flops / (chips * peaks.flops)
    return {
        **terms,
        "memory_s": t_memory_hi,  # upper bound (see docstring)
        "dominant": dom.replace("_s", "").replace("_floor", ""),
        "model_flops": model_flops,
        "useful_ratio": model_flops / hlo,
        # fraction of the compute roofline this cell achieves if the
        # dominant (floor-based) term were the runtime — structural MFU
        "roofline_fraction": ideal / bound if bound > 0 else 0.0,
    }


def load_cells(outdir: str, mesh: str) -> List[Dict]:
    d = os.path.join(outdir, mesh)
    cells = []
    if not os.path.isdir(d):
        return cells
    for f in sorted(os.listdir(d)):
        if f.endswith(".json"):
            cells.append(json.load(open(os.path.join(d, f))))
    return cells
