"""FLOPs of single layers of a prompt's prefill, from its shapes alone.

The numerators of the per-layer utilizations that read the program's
named scopes (``bench.core.scopes``). Like ``bench.core.counts`` they
count the problem, not the program: every weight of the layer multiplied
once per token, two FLOPs a multiply-add, whatever the kernels fuse or
recompute.
"""

from __future__ import annotations

__all__ = ["ffn_flops", "attn_proj_flops"]


def ffn_flops(cfg: dict, tokens: int) -> float:
    """SwiGLU FFN FLOPs over ``tokens`` tokens in every layer: ``6·d·d_ff·tokens·L``.

    Three ``d x d_ff`` matmuls (gate, up, down) a token and layer.
    """
    d = cfg["hidden_size"]
    return 6.0 * d * cfg["intermediate_size"] * tokens * cfg["num_hidden_layers"]


def attn_proj_flops(cfg: dict, tokens: int) -> float:
    """Attention projection FLOPs over ``tokens`` tokens in every layer.

    ``q`` and ``o`` are ``d x q`` matmuls and ``k`` and ``v`` are ``d x kv``
    ones: ``2·(2·d·q + 2·d·kv)·tokens·L``.
    """
    d = cfg["hidden_size"]
    hd = cfg.get("head_dim") or d // cfg["num_attention_heads"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return 2.0 * (2 * d * q + 2 * d * kv) * tokens * cfg["num_hidden_layers"]
