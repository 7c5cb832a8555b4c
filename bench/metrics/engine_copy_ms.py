"""engine_copy_ms: device time of the engine's copies around its kernel, per launch.

The self time of the ops the compiled step places under ``engine.pad``
(the trash tile appended to the state, and the copy that aliasing the
kernel's output forces) and ``engine.unpad`` (the slice back to the
domain), over the window's launches (``bench.core.scopes``).
"""

from bench.core.scopes import step_scopes

SCOPES = ("engine.pad", "engine.unpad")


def read(summary, facts, peaks):
    """Milliseconds a launch, or None when no op ran under either scope."""
    if not facts.get("launches"):
        return None
    by_scope = step_scopes(summary, facts) or {}
    copy_ns = sum(by_scope.get(s, 0.0) for s in SCOPES)
    if copy_ns <= 0:
        return None
    return copy_ns / 1e6 / facts["launches"]
