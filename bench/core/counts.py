"""Operations and bytes the work needs, from its shapes alone.

These counts are the numerators of every roofline share and utilization
the benchmark reports. They depend on the problem, never on how the
program implements it: causal attention counts the causal half whether
the kernel walks the fold, the bounding box or XLA chunks, and a simplex
launch counts its domain read and written once whatever tiles it visits.
So no change to the program can raise a share by doing more or less work.
"""

from __future__ import annotations

from math import comb

__all__ = [
    "lm_matmul_params",
    "causal_attention_flops",
    "attention_bytes",
    "prefill_flops",
    "simplex_elements",
    "simplex_blocks",
    "engine_needed_bytes",
]


def lm_matmul_params(cfg: dict) -> int:
    """Weights a dense GQA decoder multiplies per token, LM head excluded.

    ``cfg`` uses the Hugging Face keys of a Llama-style ``config.json``.
    """
    d = cfg["hidden_size"]
    hd = cfg.get("head_dim") or d // cfg["num_attention_heads"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    ff = cfg["intermediate_size"]
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * ff
    return per_layer * cfg["num_hidden_layers"]


def causal_attention_flops(batch: int, heads: int, head_dim: int,
                           seq: int) -> float:
    """FLOPs of causal attention's two matmuls over one layer.

    Query ``i`` (1-based) meets ``i`` keys: ``2·d`` FLOPs for its score
    and ``2·d`` for the weighted value, so ``4·b·h·d·s(s+1)/2``.
    """
    return 4.0 * batch * heads * head_dim * seq * (seq + 1) / 2


def attention_bytes(batch: int, heads: int, kv_heads: int, head_dim: int,
                    seq: int, itemsize: int) -> float:
    """Bytes of q, k, v and the output, each moved once, over one layer."""
    return float(itemsize * batch * seq * head_dim * (2 * heads + 2 * kv_heads))


def prefill_flops(cfg: dict, seq: int) -> float:
    """Model FLOPs to prefill one prompt of ``seq`` tokens.

    Every matmul weight twice per token, causal attention in every layer,
    and the LM head at the last position only (prefill returns its logits
    alone). Recomputed operations do not count.
    """
    d = cfg["hidden_size"]
    hd = cfg.get("head_dim") or d // cfg["num_attention_heads"]
    attn = causal_attention_flops(1, cfg["num_attention_heads"], hd, seq)
    return (2.0 * lm_matmul_params(cfg) * seq
            + cfg["num_hidden_layers"] * attn
            + 2.0 * d * cfg["vocab_size"])


def simplex_elements(m: int, n: int) -> int:
    """Elements of the m-simplex domain of side ``n``.

    ``m = 2`` is the inclusive lower triangle ``{col <= row}``,
    ``n(n+1)/2``; ``m >= 3`` is the strict simplex ``{sum < n}``,
    ``C(n+m-1, m)``.
    """
    if m == 2:
        return n * (n + 1) // 2
    return comb(n + m - 1, m)


def simplex_blocks(m: int, nb: int) -> int:
    """Tiles of side ``rho`` the domain touches, ``nb = n / rho`` per side.

    At ``m = 2`` the triangle's tiles are the ``nb(nb+1)/2`` on or below
    the diagonal; at ``m >= 3`` the tiles ``{sum(block) < nb}``.
    """
    if m == 2:
        return nb * (nb + 1) // 2
    return comb(nb + m - 1, m)


def engine_needed_bytes(m: int, n: int, itemsize: int) -> float:
    """Bytes one launch must move: each domain element read once, written once."""
    return 2.0 * itemsize * simplex_elements(m, n)
