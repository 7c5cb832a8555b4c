"""Block assembly and scanned layer stacks.

A model is: ``n_prefix`` unrolled prefix layers + ``n_periods`` scanned
repetitions of a (possibly heterogeneous) ``period`` of LayerSpecs —
scan keeps the HLO O(period) instead of O(n_layers), which is what makes
the 61-layer/80-layer dry-runs compile quickly and remat cheap.
Encoder-decoder models add a bidirectional encoder stack and per-layer
cross-attention in the decoder.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .attention import attn_apply, attn_init, full_attention, init_kv_cache
from .layers import rmsnorm, rmsnorm_init, swiglu, swiglu_init
from .mamba import init_mamba_cache, mamba_apply, mamba_init
from .mla import init_mla_cache, mla_apply, mla_init
from .moe import moe_apply, moe_init
from .xlstm import (
    init_mlstm_cache,
    init_slstm_cache,
    mlstm_apply,
    mlstm_init,
    slstm_apply,
    slstm_init,
)

__all__ = ["block_init", "block_apply", "stack_init", "stack_apply", "init_block_cache"]


def block_init(key, cfg, spec, dtype, cross: bool = False):
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"norm1": rmsnorm_init(d, dtype)}
    if spec.mixer == "attn":
        if cfg.attention == "mla":
            p["mixer"] = mla_init(ks[0], cfg, dtype)
        else:
            p["mixer"] = attn_init(ks[0], cfg, dtype)
    elif spec.mixer == "mamba":
        p["mixer"] = mamba_init(ks[0], cfg, dtype)
    elif spec.mixer == "mlstm":
        p["mixer"] = mlstm_init(ks[0], cfg, dtype)
    elif spec.mixer == "slstm":
        p["mixer"] = slstm_init(ks[0], cfg, dtype)
    else:
        raise ValueError(spec.mixer)
    if cross:
        p["norm_x"] = rmsnorm_init(d, dtype)
        p["cross"] = attn_init(ks[1], cfg, dtype)
    if spec.ffn == "dense":
        p["norm2"] = rmsnorm_init(d, dtype)
        p["ffn"] = swiglu_init(ks[2], d, cfg.d_ff, dtype)
    elif spec.ffn == "moe":
        p["norm2"] = rmsnorm_init(d, dtype)
        p["ffn"] = moe_init(ks[2], cfg, dtype)
    return p


def block_apply(
    p,
    cfg,
    spec,
    x,
    positions,
    *,
    cache=None,
    mode: str = "train",
    mesh=None,
    enc_out=None,
    cross_cache=None,
    bidirectional: bool = False,
    positions3=None,
):
    """Returns (x, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    h = rmsnorm(p["norm1"], x, cfg.norm_eps)
    mixer_cache = cache.get("mixer") if cache else None
    if spec.mixer == "attn":
        if cfg.attention == "mla":
            o, new_mixer = mla_apply(
                p["mixer"], cfg, h, positions, cache=mixer_cache, mode=mode,
                mesh=mesh,
            )
        else:
            o, new_mixer = attn_apply(
                p["mixer"],
                cfg,
                h,
                positions,
                cache=mixer_cache,
                mode=mode,
                bidirectional=bidirectional,
                positions3=positions3,
                mesh=mesh,
            )
    elif spec.mixer == "mamba":
        o, new_mixer = mamba_apply(p["mixer"], cfg, h, cache=mixer_cache, mode=mode)
    elif spec.mixer == "mlstm":
        o, new_mixer = mlstm_apply(p["mixer"], cfg, h, cache=mixer_cache, mode=mode)
    else:  # slstm
        o, new_mixer = slstm_apply(p["mixer"], cfg, h, cache=mixer_cache, mode=mode)
    x = x + o
    new_cache: Dict[str, Any] = {}
    if new_mixer is not None:
        new_cache["mixer"] = new_mixer

    if "cross" in p and enc_out is not None or cross_cache is not None:
        hx = rmsnorm(p["norm_x"], x, cfg.norm_eps)
        if cross_cache is not None:
            kv = cross_cache
        else:
            # project encoder output once (prefill / train)
            b, sk, _ = enc_out.shape
            hkv, hd = cfg.n_kv_heads, cfg.hd
            dt = x.dtype
            k = jnp.dot(enc_out, p["cross"]["wk"].astype(dt)).reshape(
                b, sk, hkv, hd
            ).transpose(0, 2, 1, 3)
            v = jnp.dot(enc_out, p["cross"]["wv"].astype(dt)).reshape(
                b, sk, hkv, hd
            ).transpose(0, 2, 1, 3)
            kv = (k, v)
            if mode in ("prefill", "decode"):
                new_cache["cross"] = kv
        o, _ = attn_apply(
            p["cross"], cfg, hx, positions, mode=mode, cross_kv=kv
        )
        x = x + o

    if spec.ffn == "dense":
        x = x + swiglu(p["ffn"], rmsnorm(p["norm2"], x, cfg.norm_eps))
    elif spec.ffn == "moe":
        o, aux = moe_apply(p["ffn"], cfg, rmsnorm(p["norm2"], x, cfg.norm_eps), mesh)
        x = x + o
    return x, new_cache, aux


def init_block_cache(cfg, spec, batch, seq, dtype, cross: bool = False):
    c: Dict[str, Any] = {}
    if spec.mixer == "attn":
        if cfg.attention == "mla":
            c["mixer"] = init_mla_cache(cfg, batch, seq, dtype)
        else:
            c["mixer"] = init_kv_cache(cfg, batch, seq, dtype)
    elif spec.mixer == "mamba":
        c["mixer"] = init_mamba_cache(cfg, batch, dtype)
    elif spec.mixer == "mlstm":
        c["mixer"] = init_mlstm_cache(cfg, batch, dtype)
    else:
        c["mixer"] = init_slstm_cache(cfg, batch, dtype)
    if cross:
        hkv, hd = cfg.n_kv_heads, cfg.hd
        c["cross"] = (
            jnp.zeros((batch, hkv, seq, hd), dtype),
            jnp.zeros((batch, hkv, seq, hd), dtype),
        )
    return c


# ---------------------------------------------------------------------------
# stacks
# ---------------------------------------------------------------------------


def stack_init(key, cfg, specs, n_periods, dtype, cross: bool = False):
    """Stacked params: each leaf gets a leading (n_periods,) dim.

    The periods are drawn under ``vmap`` over their keys, so each leaf
    is built stacked: no per-period copy is ever held beside it.
    """

    def one(k):
        ks = jax.random.split(k, len(specs))
        return {
            f"l{i}": block_init(ks[i], cfg, s, dtype, cross=cross)
            for i, s in enumerate(specs)
        }

    return jax.vmap(one)(jax.random.split(key, n_periods))


def _period_apply(cfg, specs, p, x, positions, caches, mode, mesh, enc_out,
                  bidirectional, positions3):
    new_caches = {}
    aux = jnp.zeros((), jnp.float32)
    for i, spec in enumerate(specs):
        c_i = caches.get(f"l{i}") if caches else None
        cross_cache = c_i.get("cross") if (c_i and mode == "decode") else None
        x, nc, a = block_apply(
            p[f"l{i}"],
            cfg,
            spec,
            x,
            positions,
            cache=c_i,
            mode=mode,
            mesh=mesh,
            enc_out=enc_out,
            cross_cache=cross_cache,
            bidirectional=bidirectional,
            positions3=positions3,
        )
        if mode == "decode" and c_i and "cross" in c_i:
            nc["cross"] = c_i["cross"]  # cross K/V is static during decode
        new_caches[f"l{i}"] = nc
        aux = aux + a
    return x, new_caches, aux


def stack_apply(
    params,
    cfg,
    specs,
    n_periods,
    x,
    positions,
    *,
    caches=None,
    mode: str = "train",
    mesh=None,
    enc_out=None,
    bidirectional: bool = False,
    positions3=None,
):
    """Scan the period stack.  Returns (x, new_caches, aux)."""

    def body(carry, xs):
        x, aux = carry
        p_i, c_i = xs if caches is not None else (xs, None)
        x, nc, a = _period_apply(
            cfg, specs, p_i, x, positions, c_i, mode, mesh, enc_out,
            bidirectional, positions3,
        )
        return (x, aux + a), nc

    if cfg.remat == "full":
        body = jax.checkpoint(body)
    elif cfg.remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        )

    xs = (params, caches) if caches is not None else params
    (x, aux), new_caches = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), xs)
    return x, new_caches, aux
