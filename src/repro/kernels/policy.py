"""Execution policy for every Pallas launch in the kernels package.

Historically each ``pl.pallas_call`` in this repo pinned
``interpret=True`` — correct everywhere, but it means every benchmark
number measured the Pallas *interpreter* (a Python loop per grid step),
not compiled device code.  This module is the single place that decides
how a kernel executes:

* ``default_interpret()`` — the per-backend default: the CPU backend can
  only interpret (Mosaic/Triton lowering raises ``"Only interpret mode
  is supported on CPU backend"``), so CPU resolves to ``True``;
  TPU/GPU resolve to ``False`` — the real compiled index_map path.
* ``REPRO_INTERPRET`` env var — explicit override for tests and debug:
  ``1`` forces the old always-interpret behavior, ``0`` forces the
  compiled path even on CPU (useful only to reproduce the lowering
  error; the supported compiled path on CPU is the fused-XLA executor
  in ``kernels/compiled.py``).
* ``check_tile_alignment`` — the 8x128 tiling contract the compiled
  (Mosaic) path imposes on block shapes; interpret mode accepts any
  shape, so kernels call this only when actually compiling.

Every kernel entry point takes ``interpret: bool | None = None`` and
resolves ``None`` through ``resolve_interpret`` at call time — no
``pallas_call`` site hardcodes a mode anymore.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

__all__ = [
    "default_interpret",
    "resolve_interpret",
    "backend_name",
    "check_tile_alignment",
    "tile_alignment_ok",
    "aligned_rho",
    "vmem_limit_bytes",
    "compiler_params",
    "TPU_SUBLANE",
    "TPU_LANE",
]

# Mosaic tiling contract for f32/int32 blocks: (sublane, lane) minimums.
TPU_SUBLANE = 8
TPU_LANE = 128

# Mosaic's default scoped-VMEM limit on v5e, and the most a kernel asks
# for: v5e has 128 MiB of VMEM per core; the rest stays with the compiler.
SCOPED_VMEM_DEFAULT = 16 * 2**20
SCOPED_VMEM_MAX = 100 * 2**20
# (rho,)*m 32-bit temporaries a body keeps live beside its blocks (the
# domain-mask iotas, their sum, the tile value and the select).
BODY_TEMPS = 6

_ENV = "REPRO_INTERPRET"
_TRUE = ("1", "true", "yes", "on")
_FALSE = ("0", "false", "no", "off")


def backend_name(backend: Optional[str] = None) -> str:
    """The effective JAX backend name ('cpu', 'tpu', 'gpu', ...).

    Args:
        backend: Explicit backend name, or None to ask JAX.

    Returns:
        Lower-cased backend platform name.
    """
    if backend is not None:
        return backend.lower()
    import jax

    return jax.default_backend().lower()


def default_interpret(backend: Optional[str] = None) -> bool:
    """Per-backend interpret default for every ``pallas_call`` site.

    Resolution order:

    1. ``REPRO_INTERPRET`` env var (``1``/``true`` -> interpret,
       ``0``/``false`` -> compiled) — the test/debug escape hatch.
    2. Backend capability: CPU supports only interpret mode, so it
       resolves ``True``; TPU/GPU resolve ``False`` (compiled).

    Args:
        backend: Backend name override; defaults to the active JAX
            backend.

    Returns:
        True when kernels should run under the Pallas interpreter.
    """
    env = os.environ.get(_ENV, "").strip().lower()
    if env in _TRUE:
        return True
    if env in _FALSE:
        return False
    return backend_name(backend) == "cpu"


def resolve_interpret(
    interpret: Optional[bool] = None, backend: Optional[str] = None
) -> bool:
    """Resolve a kernel's ``interpret`` argument (None -> policy default).

    Args:
        interpret: Caller-requested mode, or None for the policy.
        backend: Backend name override for the default resolution.

    Returns:
        The concrete bool to pass to ``pl.pallas_call``.
    """
    if interpret is None:
        return default_interpret(backend)
    return bool(interpret)


def check_tile_alignment(
    block_shape: Sequence[int], interpret: bool, what: str = "block"
) -> None:
    """Enforce the Mosaic 8x128 tiling contract on compiled launches.

    Interpret mode accepts any block shape (tests use tiny rho); the
    compiled path requires the last dimension to be a multiple of 128
    (lane) and the second-to-last a multiple of 8 (sublane for f32/i32).
    Raises ``ValueError`` with the offending shape instead of letting
    Mosaic fail deep inside lowering.

    Args:
        block_shape: The BlockSpec block shape about to be launched.
        interpret: Resolved interpret mode; no-op when True.
        what: Label used in the error message.
    """
    if interpret or len(block_shape) == 0:
        return
    dims = [int(d) for d in block_shape if int(d) != 1]
    if not dims:
        return
    lane = dims[-1]
    if lane % TPU_LANE != 0:
        raise ValueError(
            f"compiled (non-interpret) Pallas requires the {what} minor "
            f"dimension to be a multiple of {TPU_LANE}; got {tuple(block_shape)}. "
            f"Use aligned_rho() or run with interpret=True/REPRO_INTERPRET=1."
        )
    if len(dims) >= 2 and dims[-2] % TPU_SUBLANE != 0:
        raise ValueError(
            f"compiled (non-interpret) Pallas requires the {what} sublane "
            f"dimension to be a multiple of {TPU_SUBLANE}; got "
            f"{tuple(block_shape)}."
        )


def tile_alignment_ok(block_shape: Sequence[int]) -> bool:
    """Non-raising form of the compiled-path tiling contract.

    The static-analysis tile pass (``repro.analysis``, DESIGN.md §9)
    asks this instead of catching ``check_tile_alignment``'s
    ``ValueError`` — same rule, boolean answer.

    Args:
        block_shape: Candidate BlockSpec block shape.

    Returns:
        True when a compiled (non-interpret) launch would accept it.
    """
    try:
        check_tile_alignment(block_shape, interpret=False)
    except ValueError:
        return False
    return True


def aligned_rho(rho: int, interpret: Optional[bool] = None) -> int:
    """Round a square tile size up to the compiled-path alignment.

    Under interpret mode the requested rho is returned unchanged; on the
    compiled path rho is rounded up to the lane width (128) so a
    (rho, rho) block satisfies both the sublane and lane constraints.

    Args:
        rho: Requested square tile side.
        interpret: Resolved or requested mode (None -> policy default).

    Returns:
        A rho every compiled BlockSpec accepts.
    """
    if resolve_interpret(interpret):
        return rho
    return ((rho + TPU_LANE - 1) // TPU_LANE) * TPU_LANE


def vmem_limit_bytes(block_bytes: int, tile_bytes: int) -> Optional[int]:
    """Scoped-VMEM limit a compiled launch needs, or None for the default.

    Pallas double-buffers every block, and a body keeps a few
    tile-sized temporaries live beside them.  At m = 3 and rho = 128 one
    int32 tile is 8 MiB, so even the single-input ACCUM body outgrows
    Mosaic's 16 MiB default; the launch then asks for what it needs, up
    to ``SCOPED_VMEM_MAX``.

    Args:
        block_bytes: Bytes of one grid step's blocks (inputs + output).
        tile_bytes: Bytes of one 32-bit tile temporary.

    Returns:
        ``vmem_limit_bytes`` for ``CompilerParams``, or None when the
        default suffices.

    Example:
        >>> vmem_limit_bytes(2 * 128 * 128 * 4, 128 * 128 * 4) is None
        True
        >>> vmem_limit_bytes(2 * 128**3 * 4, 128**3 * 4) // 2**20
        100
    """
    need = 2 * block_bytes + BODY_TEMPS * tile_bytes
    if need <= SCOPED_VMEM_DEFAULT:
        return None
    return min(need + need // 4, SCOPED_VMEM_MAX)


def compiler_params(interpret: bool, block_bytes: int,
                    tile_bytes: int) -> dict:
    """``compiler_params`` for a compiled launch (empty when interpreting).

    Args:
        interpret: Resolved launch mode.
        block_bytes: Bytes of one grid step's blocks, inputs and output.
        tile_bytes: Bytes of one 32-bit tile temporary of the body.

    Returns:
        Keyword arguments for ``pallas_launch``: a scoped-VMEM limit
        where ``vmem_limit_bytes`` asks for one, else nothing.
    """
    if interpret:
        return {}
    limit = vmem_limit_bytes(block_bytes, tile_bytes)
    if limit is None:
        return {}
    from jax.experimental.pallas import tpu as pltpu

    return {"compiler_params": pltpu.CompilerParams(vmem_limit_bytes=limit)}
