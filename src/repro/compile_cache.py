"""JAX's persistent compilation cache for this repo's entry points.

Every program that compiles for the chip (``chip_smoke.py``, the serve
and train launchers, the benchmark harness, the simplex CA example)
calls ``enable_compile_cache()`` once before its first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing.  Otherwise the cache lives at a fixed path in the
checkout, ``<checkout>/.jax_cache`` (gitignored): the directory is
part of what a later run must find again, so it holds no temporary
name, process id or time.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

__all__ = ["enable_compile_cache", "CACHE_ENV"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> Optional[str]:
    """Point JAX's persistent compilation cache at its directory.

    Returns:
        The directory this call configured, or None when
        ``JAX_COMPILATION_CACHE_DIR`` is set and JAX uses it as given.
    """
    if os.environ.get(CACHE_ENV):
        return None
    import jax

    # this file is <checkout>/src/repro/compile_cache.py
    path = str(Path(__file__).resolve().parents[2] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
