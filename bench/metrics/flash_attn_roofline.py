"""flash_attn_roofline: causal attention's roofline time over the kernel's time.

In the yi-6b cells the flash attention kernel is the only Pallas kernel,
so every Pallas custom call in the window counts as attention. The least
time is the larger of the causal work (``4·b·h·d·s(s+1)/2`` FLOPs a layer)
over the bf16 peak and of q, k, v and the output moved once over the HBM
peak. The count is the same whether the fold, the bounding box or XLA
chunks compute the attention, so the share cannot pass 100%.
"""

from bench.core.counts import attention_bytes, causal_attention_flops


def read(summary, facts, peaks):
    """Percent of the roofline, or None when the window ran no Pallas kernel."""
    kernel_s = summary.kernel_ns() / 1e9
    if kernel_s <= 0 or not facts.get("prompt_lens"):
        return None
    cfg = facts["cfg"]
    hq = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // hq
    hkv = cfg["num_key_value_heads"]
    least = 0.0
    for s in facts["prompt_lens"]:
        flops = causal_attention_flops(facts["batch"], hq, hd, s)
        moved = attention_bytes(facts["batch"], hq, hkv, hd, s, facts["itemsize"])
        least += cfg["num_hidden_layers"] * max(flops / peaks.bf16_flops,
                                                moved / peaks.hbm_bytes_s)
    return 100.0 * least / kernel_s
