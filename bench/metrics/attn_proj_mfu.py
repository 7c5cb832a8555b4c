"""attn_proj_mfu: the q, k, v and o projections' FLOPs over the bf16 peak, over their time.

FLOPs are ``2·(2·d·q + 2·d·kv)`` a token and layer
(``bench.core.layer_counts``), over the prompt tokens of every request the
traced window completed. The time is the self time of the ops the compiled
step places under ``layer.attn_qkv`` and ``layer.attn_out``
(``bench.core.scopes``), the head reshapes and transposes among them.
"""

from bench.core.layer_counts import attn_proj_flops
from bench.core.scopes import step_scopes

SCOPES = ("layer.attn_qkv", "layer.attn_out")


def read(summary, facts, peaks):
    """Percent of peak, or None unless ops ran under both scopes."""
    if not facts.get("prompt_lens") or not facts.get("cfg"):
        return None
    by_scope = step_scopes(summary, facts) or {}
    if not all(by_scope.get(s, 0.0) > 0 for s in SCOPES):
        return None
    proj_s = sum(by_scope[s] for s in SCOPES) / 1e9
    tokens = facts["batch"] * sum(facts["prompt_lens"])
    return 100.0 * attn_proj_flops(facts["cfg"], tokens) / peaks.bf16_flops / proj_s
