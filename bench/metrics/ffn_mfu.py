"""ffn_mfu: the FFN's FLOPs over the chip's bf16 peak, over its device time.

FLOPs are ``6·d·d_ff`` a token and layer (``bench.core.layer_counts``),
over the prompt tokens of every request the traced window completed. The
time is the self time of the ops the compiled step places under the
program's ``layer.ffn`` scope (``bench.core.scopes``).
"""

from bench.core.layer_counts import ffn_flops
from bench.core.scopes import step_scopes


def read(summary, facts, peaks):
    """Percent of peak, or None when no op ran under ``layer.ffn``."""
    if not facts.get("prompt_lens") or not facts.get("cfg"):
        return None
    by_scope = step_scopes(summary, facts) or {}
    ffn_s = by_scope.get("layer.ffn", 0.0) / 1e9
    if ffn_s <= 0:
        return None
    tokens = facts["batch"] * sum(facts["prompt_lens"])
    return 100.0 * ffn_flops(facts["cfg"], tokens) / peaks.bf16_flops / ffn_s
