"""prefill_glue_us_per_token: device time outside the matmuls and attention, per prompt token.

The self time of the ops the compiled step places under ``layer.norm``,
``layer.rope``, ``layer.kv_cache``, ``model.embed`` and ``(unscoped)``
(the scan's per-layer weight slices, residual adds and copies XLA
inserted; ``bench.core.scopes``), over the prompt tokens of every request
the traced window completed.
"""

from bench.core.scopes import UNSCOPED, step_scopes

SCOPES = ("layer.norm", "layer.rope", "layer.kv_cache", "model.embed", UNSCOPED)


def read(summary, facts, peaks):
    """Microseconds a token, or None when the step carries no ``layer.norm``
    scope (a program without scopes is all ``(unscoped)``)."""
    if not facts.get("prompt_lens") or not facts.get("cfg"):
        return None
    by_scope = step_scopes(summary, facts) or {}
    if by_scope.get("layer.norm", 0.0) <= 0:
        return None
    glue_ns = sum(by_scope.get(s, 0.0) for s in SCOPES)
    tokens = facts["batch"] * sum(facts["prompt_lens"])
    return glue_ns / 1e3 / tokens
