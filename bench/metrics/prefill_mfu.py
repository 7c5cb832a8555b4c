"""prefill_mfu: the model FLOPs of the window's prefills over the chip's bf16 peak.

FLOPs come from ``bench.core.counts.prefill_flops`` (every matmul weight
twice per token, causal attention, the LM head at the last position),
summed over the requests the traced run completed, divided by the host
window in which it completed them and by the peak. Recomputed work does
not count, so no change can raise it by doing more.
"""

from bench.core.counts import prefill_flops


def read(summary, facts, peaks):
    """Percent of peak, or None when no request completed."""
    if not facts.get("prompt_lens"):
        return None
    flops = sum(facts["batch"] * prefill_flops(facts["cfg"], s)
                for s in facts["prompt_lens"])
    return 100.0 * flops / facts["window_s"] / peaks.bf16_flops
