"""The (m, n, backend) schedule autotuner (DESIGN.md §5).

``choose_kind`` picks which registered schedule kind a kernel should
launch for a given simplex dimension, tile count and backend — so
kernels and benchmarks never hand-pick a schedule (``kind='auto'``
everywhere, resolved through ``core.schedule.resolve_kind``).

Decision procedure:

1. **Candidates** — the kinds constructible at (m, n): the (w, h)-grid
   trio at m=2, the linear-grid kinds at m >= 3, each passed through
   ``resolve_kind`` (so 'hmap' at non-pow2 n competes as its actual
   'composite'/'rb' resolution) and deduplicated.
2. **Model scores** — ``roofline.analysis.schedule_cost_model``:
   memory-bound tile traffic (wasted steps pay full price) plus the
   per-step index-map overhead of each form (select chains, SMEM reads,
   amortized O(V) table builds).
3. **Measured ranking** — when ``compiled: true`` rows recorded in
   ``BENCH_maps.json`` (ACCUM tests, matching m/kind,
   backend-compatible, rescaled to this n by the steps ratio) cover
   *every* candidate kind, the decision ranks on them instead of the
   model; partial coverage keeps the model ranking (mixing measured
   wall-clocks with model estimates would penalize whichever kind
   happened to get benchmarked).  Provenance lands in
   ``Decision.source``.
4. **Disk cache** — decisions persist in a JSON cache keyed
   ``m,n,backend``; an entry is invalidated when the JAX version or the
   bench artifact fingerprint (content hash) changes, so fresh
   measurements re-tune automatically.  Attention keys also name the
   tile rule, so a decision made under another rule is never served.

Env knobs: ``REPRO_AUTOTUNE_CACHE`` (cache file path),
``REPRO_BENCH_ARTIFACT`` (bench rows to consume),
``REPRO_AUTOTUNE_DISABLE=1`` (skip cache reads AND writes — hermetic
test runs), ``REPRO_SPLIT_PIECES`` (force the per-piece launch split on
or off).
"""

from __future__ import annotations

import hashlib
import json
import os
import tempfile
from dataclasses import asdict, dataclass
from typing import Dict, List, Optional, Tuple

from repro.roofline.analysis import (
    LAUNCH_OVERHEAD_S,
    SELECT_S,
    schedule_cost_model,
)

__all__ = [
    "Decision",
    "AttnDecision",
    "choose_kind",
    "choose_attn_impl",
    "attn_block_q",
    "candidate_kinds",
    "should_split_pieces",
    "clear_cache",
    "cache_path",
    "bench_artifact_path",
    "CACHE_SCHEMA",
    "ATTN_INTERPRET_STEP_CAP",
]

CACHE_SCHEMA = "repro-autotune/v1"

_CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
_BENCH_ENV = "REPRO_BENCH_ARTIFACT"
_DISABLE_ENV = "REPRO_AUTOTUNE_DISABLE"
_SPLIT_ENV = "REPRO_SPLIT_PIECES"
_ATTN_CAP_ENV = "REPRO_ATTN_STEP_CAP"

# Interpret-mode flash grid-step budget: heads x grid steps above which
# the Pallas emulator (INTERPRET_STEP_S per step) would dominate and the
# decision falls back to the fused-XLA chunked path.  Irrelevant on
# compiled (TPU/GPU) backends.
ATTN_INTERPRET_STEP_CAP = 4096


@dataclass(frozen=True)
class Decision:
    """One autotuner decision record (also the on-disk cache row).

    Attributes:
        m: Simplex dimension.
        n: Tile count per side the decision applies to.
        backend: Backend the decision was made for ('cpu', 'tpu', ...).
        kind: Winning schedule kind (already ``resolve_kind``-concrete).
        source: Provenance — 'measured' (BENCH_maps.json row), 'model'
            (roofline estimate) or 'cache' (served from disk).
        score_us: Predicted/measured cost of the winner, microseconds.
        scores_us: Per-candidate scores, for inspection.
        jax_version: JAX version the decision was computed under.
        fingerprint: Bench-artifact content hash at decision time.
    """

    m: int
    n: int
    backend: str
    kind: str
    source: str
    score_us: float
    scores_us: Dict[str, float]
    jax_version: str
    fingerprint: str


def _jax_version() -> str:
    import jax

    return jax.__version__


def _backend(backend: Optional[str]) -> str:
    from repro.kernels.policy import backend_name

    return backend_name(backend)


def cache_path(path: Optional[str] = None) -> str:
    """Resolve the decision-cache file path (env-overridable).

    Args:
        path: Explicit path; wins over the env var and default.

    Returns:
        Absolute path of the JSON cache file.
    """
    p = path or os.environ.get(_CACHE_ENV) or os.path.join(
        os.path.expanduser("~"), ".cache", "repro-simplex", "autotune.json"
    )
    return os.path.abspath(p)


def bench_artifact_path(path: Optional[str] = None) -> str:
    """Resolve the bench-rows artifact path (env-overridable).

    Args:
        path: Explicit path; wins over the env var and default
            (``BENCH_maps.json`` in the working directory).

    Returns:
        Absolute path (the file may be absent — that's a valid state).
    """
    p = path or os.environ.get(_BENCH_ENV) or "BENCH_maps.json"
    return os.path.abspath(p)


def _fingerprint(path: str) -> str:
    if not os.path.isfile(path):
        return "absent"
    with open(path, "rb") as f:
        return hashlib.sha1(f.read()).hexdigest()


def _load_cache(path: str) -> Dict:
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError):
        return {"schema": CACHE_SCHEMA, "entries": {}}
    if data.get("schema") != CACHE_SCHEMA:
        return {"schema": CACHE_SCHEMA, "entries": {}}
    return data


def _store_cache(path: str, data: Dict) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path), suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(data, f, indent=1)
        os.replace(tmp, path)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass


def clear_cache(path: Optional[str] = None) -> None:
    """Delete the on-disk decision cache (tests, manual re-tune).

    Args:
        path: Cache file; defaults to ``cache_path()``.
    """
    p = cache_path(path)
    if os.path.isfile(p):
        os.unlink(p)


def candidate_kinds(m: int, n: int) -> Tuple[str, ...]:
    """Kinds that actually compete at (m, n), post-``resolve_kind``.

    m=2 restricts to the (w, h)-grid trio the 2D kernels launch; m >= 3
    uses the linear-grid kinds.  Each requested kind is resolved (e.g.
    'hmap' at non-pow2 n competes as 'composite') and duplicates drop.

    Args:
        m: Simplex dimension.
        n: Tile count per side.

    Returns:
        Ordered tuple of distinct concrete kinds.
    """
    from repro.core.schedule import registered_kinds, resolve_kind

    base = ("hmap", "rb", "bb") if m == 2 else (
        "hmap", "table", "composite", "bb"
    )
    avail = set(registered_kinds(m))
    out: List[str] = []
    for k in base:
        if k not in avail:
            continue
        r = resolve_kind(m, n, k)
        if r not in out:
            out.append(r)
    return tuple(out)


def _model_scores(m: int, n: int, kinds: Tuple[str, ...]) -> Dict[str, float]:
    """Roofline-model score (us) per candidate kind.

    The memory term is evaluated at the smallest tile a compiled kernel
    actually moves — one 8x128 VREG footprint (1024 elements) spread
    over m axes — so the per-step map overhead is weighed against
    realistic tile traffic, not toy tiles.
    """
    from repro.core.schedule import SimplexSchedule
    from repro.core.trapezoids import decompose_simplex

    from repro.kernels.policy import TPU_LANE, TPU_SUBLANE

    rho_model = max(2, round((TPU_SUBLANE * TPU_LANE) ** (1.0 / m)))
    scores = {}
    for kind in kinds:
        sched = SimplexSchedule(m, n, kind)
        pieces = len(decompose_simplex(m, n)) if kind == "composite" else 1
        s = schedule_cost_model(
            kind, sched.steps, m=m, n=n, useful=sched.useful,
            pieces=pieces, rho=rho_model,
        )
        scores[kind] = s * 1e6
    return scores


def _measured_scores(
    m: int, n: int, kinds: Tuple[str, ...], backend: str, bench_file: str
) -> Dict[str, float]:
    """Scores (us) from recorded ACCUM rows, rescaled by the steps ratio.

    Only ``compiled: true`` rows count — interpret-mode timings measure
    the Pallas emulator, not the machine the model estimates, and must
    not override it.
    """
    try:
        with open(bench_file) as f:
            artifact = json.load(f)
    except (OSError, json.JSONDecodeError):
        return {}
    from repro.core.schedule import SimplexSchedule

    best: Dict[str, Tuple[float, float]] = {}  # kind -> (|n_row - n|, us)
    for row in artifact.get("rows", []):
        test = str(row.get("test") or "")
        if not test.startswith("ACCUM"):
            continue
        if row.get("m") != m or row.get("map") not in kinds:
            continue
        if not row.get("compiled"):
            continue
        row_backend = row.get("backend")
        if row_backend is not None and row_backend != backend:
            continue
        us = row.get("us_per_call")
        steps_row = row.get("grid_steps")
        if not us or not steps_row:
            continue
        kind = row["map"]
        here = SimplexSchedule(m, n, kind).steps
        scaled = float(us) * here / float(steps_row)
        dist = abs(float(steps_row) - here)
        if kind not in best or dist < best[kind][0]:
            best[kind] = (dist, scaled)
    return {k: v[1] for k, v in best.items()}


def choose_kind(
    m: int,
    n: int,
    backend: Optional[str] = None,
    *,
    bench_path: Optional[str] = None,
    cache_file: Optional[str] = None,
    refresh: bool = False,
) -> Decision:
    """Pick the schedule kind for (m, n, backend); cache on disk.

    Args:
        m: Simplex dimension (m >= 2).
        n: Tile count per side.
        backend: Backend name; None uses the active JAX backend.
        bench_path: Bench artifact override (else env/default).
        cache_file: Cache file override (else env/default).
        refresh: Recompute even on a fresh cache hit.

    Returns:
        The winning ``Decision`` (``.kind`` is what kernels launch).

    Example:
        >>> import os
        >>> _old = os.environ.get("REPRO_AUTOTUNE_DISABLE")
        >>> os.environ["REPRO_AUTOTUNE_DISABLE"] = "1"  # hermetic
        >>> d = choose_kind(3, 8, backend="cpu")
        >>> d.kind in candidate_kinds(3, 8) and d.source != "cache"
        True
        >>> _ = (os.environ.pop("REPRO_AUTOTUNE_DISABLE") if _old is None
        ...      else os.environ.update(REPRO_AUTOTUNE_DISABLE=_old))
    """
    backend = _backend(backend)
    disabled = os.environ.get(_DISABLE_ENV, "").strip() == "1"
    bench_file = bench_artifact_path(bench_path)
    cpath = cache_path(cache_file)
    key = f"m={m},n={n},backend={backend}"
    fp = _fingerprint(bench_file)
    jv = _jax_version()

    if not disabled and not refresh:
        entry = _load_cache(cpath)["entries"].get(key)
        if (
            entry is not None
            and entry.get("jax_version") == jv
            and entry.get("fingerprint") == fp
        ):
            return Decision(
                m=m, n=n, backend=backend, kind=entry["kind"],
                source="cache", score_us=entry["score_us"],
                scores_us=entry.get("scores_us", {}),
                jax_version=jv, fingerprint=fp,
            )

    kinds = candidate_kinds(m, n)
    scores = _model_scores(m, n, kinds)
    measured = _measured_scores(m, n, kinds, backend, bench_file)
    # Rank on measured times only when EVERY candidate has one —
    # measured wall-clocks (whole-executor) and model estimates
    # (schedule overhead) are different units, and overriding a single
    # kind would penalize whichever kind happened to get benchmarked.
    use_measured = set(kinds) <= set(measured)
    merged = dict(measured) if use_measured else scores
    winner = min(merged, key=merged.get)
    decision = Decision(
        m=m, n=n, backend=backend, kind=winner,
        source="measured" if use_measured else "model",
        score_us=merged[winner], scores_us=merged,
        jax_version=jv, fingerprint=fp,
    )
    if not disabled:
        cache = _load_cache(cpath)
        row = asdict(decision)
        del row["m"], row["n"], row["backend"]
        cache["entries"][key] = row
        _store_cache(cpath, cache)
    return decision


# ---------------------------------------------------------------------------
# Attention-impl decisions (the serving hot path — DESIGN.md §8)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class AttnDecision:
    """One causal-attention dispatch decision (and its cache row).

    Attributes:
        seq: Sequence length the decision applies to.
        heads: Query-head count (per example).
        head_dim: Attention head dimension D.
        backend: Backend the decision was made for.
        impl: Winning executor — ``'flash'`` (simplex-grid Pallas
            kernel) or ``'chunked'`` (fused-XLA fallback).
        kind: Schedule of the winner: ``'folded'`` / ``'bb'`` for
            flash, ``'chunked'`` for the XLA path.
        block_q: Square tile side the flash kernel should launch with
            (also the chunk for the XLA path); 0 when no tile divides
            the sequence.
        source: Provenance — 'measured', 'model', 'cache' or
            'fallback' (shape unmappable by the flash kernel).
        score_us: Predicted/measured cost of the winner, microseconds.
        scores_us: Per-candidate scores, for inspection.
        jax_version: JAX version at decision time.
        fingerprint: Bench-artifact content hash at decision time.
    """

    seq: int
    heads: int
    head_dim: int
    backend: str
    impl: str
    kind: str
    block_q: int
    source: str
    score_us: float
    scores_us: Dict[str, float]
    jax_version: str
    fingerprint: str


# Tile sides, largest first.  A grid step of the flash kernel pays a
# fixed cost beside its matmuls (about 0.54 us a 128 step on a v5e,
# against 43 ns of bf16 matmul: PERF.md §6), so a larger tile pays it
# less often.  The interpreter keeps the 128 cap: its per-step cost is
# emulation, which no chip measurement speaks for.
_ATTN_BLOCKS = (512, 256, 128, 64, 32, 16, 8)
_ATTN_INTERPRET_MAX = 128
# Part of every attention decision's cache key: change it with the tile
# rule, so that no decision of another rule is served, nor is one of
# this rule served to code that keeps another.
_ATTN_TILE_RULE = "tiles=512"


def attn_block_q(seq: int, head_dim: int, backend: Optional[str] = None) -> int:
    """Square attention tile side for a sequence length (0 if none fits).

    Compiled backends take the largest aligned divisor of ``seq`` up to
    512: the biggest tile walks the fewest grid steps, and each step's
    fixed cost outweighs the larger tile's extra work on the diagonal.
    Interpret backends take divisors up to 128, and prefer the largest
    that still yields at least two query tiles, so the folded simplex
    walk is actually exercised rather than degenerating to the
    single-tile bounding box.

    Args:
        seq: Sequence length.
        head_dim: Attention head dim (alignment on the compiled path).
        backend: Backend name; None uses the active JAX backend.

    Returns:
        The chosen tile side, or 0 when no candidate divides ``seq``
        (the dispatch then falls back to the chunked XLA path).

    Example:
        >>> attn_block_q(64, 16, backend="cpu")   # interpret: nq=2 fold
        32
        >>> attn_block_q(8192, 128, backend="tpu")
        512
        >>> attn_block_q(1920, 128, backend="tpu")  # 15 x 128: keeps 128
        128
    """
    from repro.kernels.policy import TPU_LANE, TPU_SUBLANE, default_interpret

    interpret = default_interpret(backend)
    top = _ATTN_INTERPRET_MAX if interpret else seq
    divisors = [bq for bq in _ATTN_BLOCKS if bq <= top and seq % bq == 0]
    if not interpret:
        divisors = [
            bq for bq in divisors
            if bq % TPU_SUBLANE == 0 and head_dim % TPU_LANE == 0
        ]
    if not divisors:
        return 0
    if interpret:
        for bq in divisors:
            if seq // bq >= 2:
                return bq
    return divisors[0]


def _attn_model_scores(
    nq: int, heads: int, head_dim: int, block_q: int
) -> Dict[str, float]:
    """Analytic prior (us) per attention executor at device constants.

    Uses the roofline attention entries (``schedule_cost_model`` with
    ``attn-*`` kinds): the fold halves block-pair visits vs the
    bounding box, and the chunked XLA path pays the score-tile HBM
    round-trip flash keeps in VMEM.
    """
    from repro.kernels.flash_attention import flash_grid_steps

    tri = nq * (nq + 1) // 2
    scores = {}
    for kind in ("folded", "bb", "chunked"):
        steps = heads * flash_grid_steps(
            nq, "bb" if kind == "bb" else "folded"
        )
        scores[kind] = schedule_cost_model(
            f"attn-{kind}", steps, m=2, n=nq, useful=heads * tri,
            rho=block_q, head_dim=head_dim,
        ) * 1e6
    return scores


def _measured_attn_scores(
    nq: int, heads: int, kinds: Tuple[str, ...], backend: str, bench_file: str
) -> Dict[str, float]:
    """Scores (us) from recorded ATTN rows, rescaled by the steps ratio.

    Mirrors ``_measured_scores``: only ``compiled: true`` rows count
    (interpret-mode wall-clocks measure the emulator, not the machine).
    """
    try:
        with open(bench_file) as f:
            artifact = json.load(f)
    except (OSError, json.JSONDecodeError):
        return {}
    from repro.kernels.flash_attention import flash_grid_steps

    best: Dict[str, Tuple[float, float]] = {}
    for row in artifact.get("rows", []):
        if row.get("test") != "ATTN" or row.get("map") not in kinds:
            continue
        if not row.get("compiled"):
            continue
        row_backend = row.get("backend")
        if row_backend is not None and row_backend != backend:
            continue
        us = row.get("us_per_call")
        steps_row = row.get("grid_steps")
        if not us or not steps_row:
            continue
        kind = row["map"]
        here = heads * flash_grid_steps(nq, "bb" if kind == "bb" else "folded")
        scaled = float(us) * here / float(steps_row)
        dist = abs(float(steps_row) - here)
        if kind not in best or dist < best[kind][0]:
            best[kind] = (dist, scaled)
    return {k: v[1] for k, v in best.items()}


def choose_attn_impl(
    seq: int,
    heads: int,
    head_dim: int,
    backend: Optional[str] = None,
    *,
    bench_path: Optional[str] = None,
    cache_file: Optional[str] = None,
    refresh: bool = False,
) -> AttnDecision:
    """Pick the causal-attention executor for (seq, heads, head_dim).

    The dispatch decision ``models.attention.simplex_attention`` (and
    ``ops.causal_flash_attention`` with ``kind='auto'``) resolves
    through — cached on disk next to the schedule decisions.  Ranking:
    measured ``compiled: true`` ATTN rows from ``BENCH_maps.json`` when
    they cover every candidate, else the roofline attention prior
    (``schedule_cost_model`` ``attn-*`` entries).  Two structural
    guards override the ranking:

    * no candidate tile divides ``seq`` (or the compiled path's 8x128
      alignment fails) — the flash kernel cannot map the shape, so the
      chunked XLA path wins as ``source='fallback'``;
    * on interpret backends, ``heads x grid_steps`` beyond
      ``ATTN_INTERPRET_STEP_CAP`` (env ``REPRO_ATTN_STEP_CAP``) — the
      Pallas emulator pays ``INTERPRET_STEP_S`` per step, so huge
      grids go to the chunked path; production (TPU/GPU) ignores the
      cap.

    Args:
        seq: Sequence length (static under jit — decisions happen at
            trace time).
        heads: Query-head count per example.
        head_dim: Attention head dimension.
        backend: Backend name; None uses the active JAX backend.
        bench_path: Bench artifact override (else env/default).
        cache_file: Cache file override (else env/default).
        refresh: Recompute even on a fresh cache hit.

    Returns:
        The winning ``AttnDecision`` (``.impl``/``.kind``/``.block_q``
        are what the dispatch launches).

    Example:
        >>> import os
        >>> _old = os.environ.get("REPRO_AUTOTUNE_DISABLE")
        >>> os.environ["REPRO_AUTOTUNE_DISABLE"] = "1"  # hermetic
        >>> d = choose_attn_impl(64, 4, 16, backend="cpu")
        >>> (d.impl, d.kind, 64 % d.block_q)
        ('flash', 'folded', 0)
        >>> _ = (os.environ.pop("REPRO_AUTOTUNE_DISABLE") if _old is None
        ...      else os.environ.update(REPRO_AUTOTUNE_DISABLE=_old))
    """
    from repro.kernels.flash_attention import flash_grid_steps
    from repro.kernels.policy import default_interpret

    backend = _backend(backend)
    disabled = os.environ.get(_DISABLE_ENV, "").strip() == "1"
    bench_file = bench_artifact_path(bench_path)
    cpath = cache_path(cache_file)
    key = (f"attn,s={seq},h={heads},d={head_dim},backend={backend},"
           f"{_ATTN_TILE_RULE}")
    fp = _fingerprint(bench_file)
    jv = _jax_version()

    if not disabled and not refresh:
        entry = _load_cache(cpath)["entries"].get(key)
        if (
            entry is not None
            and entry.get("jax_version") == jv
            and entry.get("fingerprint") == fp
        ):
            return AttnDecision(
                seq=seq, heads=heads, head_dim=head_dim, backend=backend,
                impl=entry["impl"], kind=entry["kind"],
                block_q=entry["block_q"], source="cache",
                score_us=entry["score_us"],
                scores_us=entry.get("scores_us", {}),
                jax_version=jv, fingerprint=fp,
            )

    interpret = default_interpret(backend)
    block = attn_block_q(seq, head_dim, backend)
    nq = seq // block if block else 0
    flash_ok = block > 0
    if flash_ok and interpret:
        cap = int(os.environ.get(_ATTN_CAP_ENV, "") or ATTN_INTERPRET_STEP_CAP)
        flash_ok = heads * flash_grid_steps(nq, "folded") <= cap

    if not flash_ok:
        decision = AttnDecision(
            seq=seq, heads=heads, head_dim=head_dim, backend=backend,
            impl="chunked", kind="chunked", block_q=block,
            source="fallback", score_us=0.0, scores_us={},
            jax_version=jv, fingerprint=fp,
        )
    else:
        kinds = ("folded", "bb", "chunked")
        scores = _attn_model_scores(nq, heads, head_dim, block)
        measured = _measured_attn_scores(nq, heads, kinds, backend, bench_file)
        use_measured = set(kinds) <= set(measured)
        merged = dict(measured) if use_measured else scores
        winner = min(merged, key=merged.get)
        decision = AttnDecision(
            seq=seq, heads=heads, head_dim=head_dim, backend=backend,
            impl="chunked" if winner == "chunked" else "flash",
            kind=winner, block_q=block,
            source="measured" if use_measured else "model",
            score_us=merged[winner], scores_us=merged,
            jax_version=jv, fingerprint=fp,
        )
    if not disabled:
        cache = _load_cache(cpath)
        row = asdict(decision)
        for drop in ("seq", "heads", "head_dim", "backend"):
            del row[drop]
        cache["entries"][key] = row
        _store_cache(cpath, cache)
    return decision


def should_split_pieces(n_pieces: int, steps: int) -> bool:
    """Split a composite schedule into per-piece launches?

    The branchless composite map pays an O(pieces) select chain on
    every grid step; splitting removes the chain at the cost of one
    extra launch per piece.  Per extra launch the saving is
    ``steps * SELECT_S`` (each remaining launch drops ~one chain
    element per step), so split when that exceeds
    ``LAUNCH_OVERHEAD_S`` — and only when there are enough pieces for
    the chain to matter.  ``REPRO_SPLIT_PIECES=1/0`` forces it.

    Args:
        n_pieces: Piece count of the decomposition.
        steps: Total grid steps of the unsplit schedule.

    Returns:
        True when per-piece launches are predicted to win.

    Example:
        >>> should_split_pieces(2, 10**6), should_split_pieces(30, 10**6)
        (False, True)
    """
    env = os.environ.get(_SPLIT_ENV, "").strip()
    if env == "1":
        return True
    if env == "0":
        return False
    if n_pieces < 4:
        return False
    return steps * SELECT_S > LAUNCH_OVERHEAD_S
