"""Plain reference of yi-6b (a Llama-architecture decoder) and its seeded weights.

Written from the published architecture (arXiv:2403.04652; the Llama
layer of Hugging Face ``LlamaForCausalLM``), independently of the program
under test: token embedding, then per layer
``x += Wo·attn(rope(Wq·n1(x)), rope(Wk·n1(x)), Wv·n1(x))`` and
``x += W2·(silu(W1·n2(x)) * W3·n2(x))`` with RMSNorm ``n(x) = x /
sqrt(mean(x^2) + eps) * g``, causal grouped-query attention scaled by
``head_dim^-1/2`` (query head ``h`` reads kv head ``h // (heads / kv_heads)``),
rotary embedding on the two halves of each head with inverse frequencies
``theta^(-i / (head_dim/2))``, a final RMSNorm and an untied LM head.

The weights are drawn here from the seed, one layer at a time, and the
benchmark lays the same draws out for the program. The reference computes
in float32 at ``highest`` matmul precision, layer by layer, on a block of
prompts at a time (at most ``ROWS`` tokens), with attention taken one
prompt and one query block at a time, so that it fits on one chip once
the program is freed. ``precision="int8"`` is the control: every matmul's
operands rounded to int8 (activations per row, weights per output column),
the step below the bfloat16 the configuration states.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["dims", "layer_weights", "global_weights", "last_logits"]

HIGHEST = jax.lax.Precision.HIGHEST
ROWS = 32768


def dims(cfg: dict) -> tuple:
    """``(d, heads, kv_heads, head_dim, d_ff, vocab, layers, eps, theta)``."""
    d = cfg["hidden_size"]
    hq = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // hq
    return (d, hq, cfg["num_key_value_heads"], hd, cfg["intermediate_size"],
            cfg["vocab_size"], cfg["num_hidden_layers"],
            float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]))


def _layer_shapes(dm: tuple) -> dict:
    d, hq, hkv, hd, ff = dm[:5]
    return {"norm1": (d,), "wq": (d, hq * hd), "wk": (d, hkv * hd),
            "wv": (d, hkv * hd), "wo": (hq * hd, d), "norm2": (d,),
            "w1": (d, ff), "w3": (d, ff), "w2": (ff, d)}


def _draw(key, name: str, shape: tuple):
    if name.startswith("norm"):
        w = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    else:
        w = jax.random.normal(key, shape, jnp.float32) * shape[0] ** -0.5
    return w.astype(jnp.bfloat16)


def layer_weights(dm: tuple, key, i):
    """Layer ``i``'s weights in bfloat16; ``i`` may be traced (vmap, jit)."""
    k = jax.random.fold_in(jax.random.fold_in(key, 1), i)
    return {name: _draw(jax.random.fold_in(k, j), name, shape)
            for j, (name, shape) in enumerate(sorted(_layer_shapes(dm).items()))}


def global_weights(dm: tuple, key):
    """Embedding ``(vocab, d)``, final norm gain ``(d,)``, LM head ``(d, vocab)``."""
    d, vocab = dm[0], dm[5]
    k = jax.random.fold_in(key, 0)
    embed = jax.random.normal(jax.random.fold_in(k, 0), (vocab, d), jnp.float32)
    return {"embed": embed.astype(jnp.bfloat16),
            "final_norm": _draw(jax.random.fold_in(k, 1), "norm", (d,)),
            "unembed": _draw(jax.random.fold_in(k, 2), "unembed", (d, vocab))}


def _q8(a, axis):
    """Round ``a`` to int8 levels, one symmetric scale along ``axis``'s slices."""
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(a / s) * s


def _mm(a, b, precision: str):
    """``a @ b`` in float32, operands rounded to int8 first under the control."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if precision == "int8":
        a, b = _q8(a, -1), _q8(b, 0)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """x ``(h, s, hd)``; rotation of the two halves of each head."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, precision, q_block):
    """Causal attention of one prompt: q ``(hkv, g, s, hd)``, k and v ``(hkv, s, hd)``."""
    hkv, g, s, hd = q.shape
    pos = jnp.arange(s)
    q = q.reshape(hkv, g, s // q_block, q_block, hd)
    if precision == "int8":
        k = _q8(k, -1)
        v = _q8(v, 1)

    def block(b):
        qb = q[:, :, b] * hd ** -0.5
        if precision == "int8":
            qb = _q8(qb, -1)
        sc = jnp.einsum("hgqd,hkd->hgqk", qb, k, precision=HIGHEST)
        qpos = b * q_block + jnp.arange(q_block)
        sc = jnp.where(pos[None, :] <= qpos[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, -1)
        if precision == "int8":
            p = _q8(p, -1)
        return jnp.einsum("hgqk,hkd->hgqd", p, v, precision=HIGHEST)

    o = jax.lax.map(block, jnp.arange(s // q_block))  # (nb, hkv, g, qb, hd)
    return o.transpose(0, 3, 1, 2, 4).reshape(s, hkv * g * hd)


@partial(jax.jit, static_argnames=("dm", "precision", "q_block"))
def _layer(x, w, dm, precision, q_block):
    """One decoder layer on ``x`` ``(prompts, s, d)`` float32."""
    d, hq, hkv, hd, _, _, _, eps, theta = dm
    b, s = x.shape[:2]
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    pos = jnp.arange(s)
    h = _rmsnorm(x, w["norm1"], eps)
    q = _mm(h, w["wq"], precision).reshape(b, s, hq, hd).transpose(0, 2, 1, 3)
    k = _mm(h, w["wk"], precision).reshape(b, s, hkv, hd).transpose(0, 2, 1, 3)
    v = _mm(h, w["wv"], precision).reshape(b, s, hkv, hd).transpose(0, 2, 1, 3)
    q = _rope(q, pos, theta).reshape(b, hkv, hq // hkv, s, hd)
    k = _rope(k, pos, theta)
    o = jax.lax.map(lambda qkv: _attend(*qkv, precision, q_block), (q, k, v))
    x = x + _mm(o, w["wo"], precision)
    h = _rmsnorm(x, w["norm2"], eps)
    f = jax.nn.silu(_mm(h, w["w1"], precision)) * _mm(h, w["w3"], precision)
    return x + _mm(f, w["w2"], precision)


_layer_weights_jit = jax.jit(layer_weights, static_argnums=0)
_global_weights_jit = jax.jit(global_weights, static_argnums=0)


def _last_hidden(cfg: dict, key, tokens, precision: str):
    """Final-normed hidden state at the last position, ``(prompts, d)`` float32."""
    dm = dims(cfg)
    s = tokens.shape[-1]
    q_block = next(b for b in (1024, 512, 256, 128, 64, 32, 16, 8, 4, 2, 1)
                   if s % b == 0)
    gw = _global_weights_jit(dm, key)
    x = gw["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        for i in range(dm[6]):
            x = _layer(x, _layer_weights_jit(dm, key, i), dm, precision, q_block)
    return _rmsnorm(x[:, -1], gw["final_norm"].astype(jnp.float32), dm[7]), gw["unembed"]


def last_logits(cfg: dict, key, tokens, precision: str = "float32") -> np.ndarray:
    """Logits ``(prompts, vocab)`` float32 at the last position of each prompt.

    Args:
        cfg: The configuration (Hugging Face keys).
        key: The weights' key (``bench.core.harness.seed_key``).
        tokens: Token ids ``(prompts, s)`` of prompts of one length.
        precision: ``"float32"`` (the reference) or ``"int8"`` (the control).
    """
    tokens = np.asarray(tokens, np.int32)
    per = max(1, ROWS // tokens.shape[1])
    out = []
    for i in range(0, tokens.shape[0], per):
        h, unembed = _last_hidden(cfg, key, tokens[i:i + per], precision)
        out.append(np.asarray(_mm(h, unembed, precision)))
    return np.concatenate(out)
