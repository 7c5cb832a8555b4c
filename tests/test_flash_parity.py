"""Flash-attention parity suite (ISSUE 9, DESIGN.md §8).

Differential triangle: the Pallas flash kernel (both simplex schedules,
interpret mode on this host) vs the chunked XLA realization vs a
float64 numpy softmax oracle — across even/odd tile counts, GQA
ratios, head dims, additive bias and segment masking.  Flash and
chunked share tile size, reduction order and f32 accumulation, so the
suite asserts BIT-parity between them (the acceptance bar for swapping
the serving hot path), and oracle-closeness at f32 tolerance.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention
from repro.models.attention import (
    attn_apply,
    attn_init,
    chunked_causal_attention,
    simplex_attention,
)


def _qkv(b, hq, hkv, s, d, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, d), dtype)
    return q, k, v


def np_causal_attention(q, k, v, bias=None, segment_ids=None):
    """Float64 softmax oracle (GQA-aware, optional bias/segments)."""
    q = np.asarray(q, np.float64)
    k = np.asarray(k, np.float64)
    v = np.asarray(v, np.float64)
    b, hq, s, d = q.shape
    g = hq // k.shape[1]
    kq = np.repeat(k, g, axis=1)
    vq = np.repeat(v, g, axis=1)
    sc = np.einsum("bhqd,bhkd->bhqk", q, kq) * d**-0.5
    mask = np.tril(np.ones((s, s), bool))[None, None]
    if bias is not None:
        sc = sc + np.asarray(bias, np.float64)
    if segment_ids is not None:
        seg = np.asarray(segment_ids)
        mask = mask & (seg[:, None, :, None] == seg[:, None, None, :])
    sc = np.where(mask, sc, -np.inf)
    sc = sc - sc.max(-1, keepdims=True)
    p = np.exp(sc)
    tot = p.sum(-1, keepdims=True)
    p = np.where(tot > 0, p / np.where(tot == 0, 1.0, tot), 0.0)
    return np.einsum("bhqk,bhkd->bhqd", p, vq)


@pytest.mark.parametrize("s,block", [(64, 16), (48, 16)])  # nq 4 | 3
@pytest.mark.parametrize("gqa", [1, 4])
@pytest.mark.parametrize("d", [64, 128])
def test_flash_vs_chunked_vs_oracle(s, block, gqa, d):
    hq = 4
    q, k, v = _qkv(2, hq, hq // gqa, s, d, seed=s + gqa + d)
    want = np_causal_attention(q, k, v)
    ch = chunked_causal_attention(q, k, v, chunk=block)
    np.testing.assert_allclose(np.asarray(ch), want, atol=2e-5, rtol=2e-5)
    for kind in ("folded", "bb"):
        fl = flash_attention(q, k, v, kind=kind, block_q=block, block_kv=block)
        # same tiling + reduction order + f32 accumulation -> bit-equal
        assert np.array_equal(np.asarray(fl), np.asarray(ch)), kind


@pytest.mark.parametrize("gqa", [1, 4])
def test_flash_tile_size_keeps_oracle_parity(gqa):
    # the same inputs at two square tiles (nq 2 and 8): only the order
    # of accumulation changes, so both stay on the float64 oracle.
    s, hq = 128, 4
    q, k, v = _qkv(2, hq, hq // gqa, s, 64, seed=11 + gqa)
    want = np_causal_attention(q, k, v)
    for block in (s // 2, s // 8):
        got = flash_attention(
            q, k, v, kind="folded", block_q=block, block_kv=block
        )
        np.testing.assert_allclose(
            np.asarray(got), want, atol=2e-5, rtol=2e-5, err_msg=str(block)
        )


def test_flash_additive_bias_matches_oracle():
    s, block = 64, 32
    q, k, v = _qkv(2, 4, 1, s, 64, seed=7)
    bias = jax.random.normal(jax.random.PRNGKey(8), (2, 1, s, s), jnp.float32)
    want = np_causal_attention(q, k, v, bias=np.asarray(bias))
    for kind in ("folded", "bb"):
        got = flash_attention(
            q, k, v, bias=bias, kind=kind, block_q=block, block_kv=block
        )
        np.testing.assert_allclose(
            np.asarray(got), want, atol=2e-5, rtol=2e-5
        )


def test_flash_segment_masking_matches_oracle():
    s, block = 64, 16
    q, k, v = _qkv(1, 4, 2, s, 64, seed=9)
    seg = jnp.asarray(
        np.repeat(np.arange(4), s // 4)[None].repeat(1, 0), jnp.int32
    )
    want = np_causal_attention(q, k, v, segment_ids=np.asarray(seg))
    for kind in ("folded", "bb"):
        got = flash_attention(
            q, k, v, segment_ids=seg, kind=kind,
            block_q=block, block_kv=block,
        )
        np.testing.assert_allclose(
            np.asarray(got), want, atol=2e-5, rtol=2e-5
        )


def test_simplex_attention_dispatch_bit_parity(monkeypatch):
    # the dispatch's flash result must bit-match chunked at the tile the
    # decision picked — the hot-path swap is invisible numerically.
    monkeypatch.setenv("REPRO_AUTOTUNE_DISABLE", "1")
    from repro.autotune import choose_attn_impl

    q, k, v = _qkv(4, 4, 1, 64, 16, seed=1)
    dec = choose_attn_impl(64, 4, 16)
    assert dec.impl == "flash" and dec.kind == "folded"
    fl = simplex_attention(q, k, v, impl="flash")
    ch = chunked_causal_attention(q, k, v, chunk=dec.block_q)
    assert np.array_equal(np.asarray(fl), np.asarray(ch))


def test_simplex_attention_mla_shape_falls_back(monkeypatch):
    # v_head_dim != qk head dim (MLA): flash cannot map it.  Under
    # impl='auto' the dispatch returns the chunked result and says so;
    # a forced impl='flash' raises instead of falling back.
    monkeypatch.setenv("REPRO_AUTOTUNE_DISABLE", "1")
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, 4, 64, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, 4, 64, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 4, 64, 48), jnp.float32)  # dv != d
    with pytest.warns(UserWarning, match="value head dim 48"):
        got = simplex_attention(q, k, v, impl="auto")
    want = chunked_causal_attention(q, k, v)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    with pytest.raises(ValueError, match="value head dim 48"):
        simplex_attention(q, k, v, impl="flash")


@pytest.mark.parametrize("impl", ["flash", "flash-folded", "flash-bb"])
def test_forced_flash_raises_on_unmappable_shape(monkeypatch, impl):
    # s=36: no flash tile (128..8) divides it.  Only 'auto' may go
    # chunked; a forced flash impl must not.
    monkeypatch.setenv("REPRO_AUTOTUNE_DISABLE", "1")
    q, k, v = _qkv(1, 4, 1, 36, 16)
    with pytest.raises(ValueError, match="no flash tile divides s=36"):
        simplex_attention(q, k, v, impl=impl)
    with pytest.warns(UserWarning, match="no flash tile divides s=36"):
        got = simplex_attention(q, k, v, impl="auto")
    assert got.shape == q.shape


def test_simplex_attention_rejects_unknown_impl():
    q, k, v = _qkv(1, 2, 2, 16, 8)
    with pytest.raises(ValueError, match="impl"):
        simplex_attention(q, k, v, impl="mystery")


class _Cfg:
    d_model = 64
    n_heads = 4
    n_kv_heads = 1
    hd = 16
    rope_theta = 10_000.0
    mrope_sections = None
    attention_chunk = 512
    attention_schedule = "folded"
    attention_impl = "auto"


def test_attn_apply_decode_matches_prefill(monkeypatch):
    # decode (KV-cache strip path) must agree with the flash prefill on
    # the same token: run prefill over s+1 tokens, and separately
    # prefill s then decode token s against the cache.
    monkeypatch.setenv("REPRO_AUTOTUNE_DISABLE", "1")
    cfg = _Cfg()
    s = 64
    p = attn_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, s + 1, cfg.d_model))
    pos = jnp.arange(s + 1)[None].repeat(2, 0)

    full, _ = attn_apply(p, cfg, x, pos, mode="train")
    _, cache = attn_apply(p, cfg, x[:, :s], pos[:, :s], mode="prefill")
    dec, _ = attn_apply(
        p, cfg, x[:, s:], pos[:, s:], mode="decode", cache=cache
    )
    np.testing.assert_allclose(
        np.asarray(dec[:, 0]), np.asarray(full[:, s]), atol=2e-5, rtol=2e-5
    )


@pytest.mark.parametrize("kind", ["folded", "bb"])
def test_flash_grad_matches_chunked(kind):
    # training goes through jax.grad: the custom-VJP backward (XLA
    # reference attention) must agree with AD through the chunked walk.
    q, k, v = _qkv(2, 4, 2, 48, 32, seed=7)

    def flash_loss(q, k, v):
        out = flash_attention(q, k, v, kind=kind, block_q=16, block_kv=16)
        return (out * out).sum()

    def chunk_loss(q, k, v):
        out = chunked_causal_attention(q, k, v, chunk=16)
        return (out * out).sum()

    gf = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    gc = jax.grad(chunk_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gc):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-5
        )


def test_flash_grad_bias_and_segments():
    # bias cotangent flows; int segment ids take a float0 cotangent
    # (i.e. grad simply works in a packed-training step).
    q, k, v = _qkv(2, 4, 1, 32, 16, seed=8)
    bias = jax.random.normal(jax.random.PRNGKey(9), (2, 1, 32, 32))
    seg = jnp.concatenate(
        [jnp.zeros((2, 16), jnp.int32), jnp.ones((2, 16), jnp.int32)], axis=1
    )

    def loss(q, k, v, bias):
        out = flash_attention(
            q, k, v, bias=bias, segment_ids=seg,
            kind="folded", block_q=16, block_kv=16,
        )
        return (out * out).sum()

    grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))(q, k, v, bias)
    for g in grads:
        assert bool(jnp.isfinite(g).all())
    assert grads[3].shape == bias.shape
