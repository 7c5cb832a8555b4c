"""Attention substrate: XLA chunked attention with the paper's folded
simplex schedule, GQA layers, caches, cross- and bidirectional attention.

The folded schedule is the framework's first-class use of the paper's
contribution (DESIGN.md §2): causal attention's (q_tile, kv_tile)
iteration space is a standard 2-simplex; the bounding-box schedule
(``'bb'``) walks the full nq x nq tile grid and masks, spending ~2x the
FLOPs; the folded schedule walks the zero-waste super-orthotope
(nq/2 pairs x nq+1 steps) — HLO dot FLOPs drop by ~2x, visible directly
in the dry-run cost analysis.  On real TPU the same schedule runs as the
Pallas kernel (kernels/flash_attention.py); this module is the portable
XLA realization used by the distributed model.
"""

from __future__ import annotations

import warnings
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .layers import dense_init, rope

NEG_INF = -1e30

__all__ = [
    "chunked_causal_attention",
    "simplex_attention",
    "full_attention",
    "decode_attention",
    "attn_init",
    "attn_apply",
    "init_kv_cache",
]


def _best_chunk(s: int, chunk: int) -> int:
    """Largest divisor of s that is <= chunk (odd tails, e.g. MTP's S-1)."""
    chunk = min(chunk, s)
    while s % chunk:
        chunk -= 1
    return chunk


def _gqa_scores(qg, kb):
    """qg: (B, Hkv, G, ..., bq, D), kb: (B, Hkv, ..., bk, D) -> scores f32."""
    return jnp.einsum(
        "bhg...qd,bh...kd->bhg...qk", qg, kb, preferred_element_type=jnp.float32
    )


def _gqa_out(pr, vb):
    return jnp.einsum(
        "bhg...qk,bh...kd->bhg...qd",
        pr.astype(vb.dtype),
        vb,
        preferred_element_type=jnp.float32,
    )


def chunked_causal_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    chunk: int = 512,
    schedule: str = "folded",
    scale: Optional[float] = None,
) -> jax.Array:
    """Causal self-attention, GQA aware, O(S * chunk) live memory.

    q: (B, Hq, S, D); k, v: (B, Hkv, S, D).  schedule:
      'folded' — simplex walk, ~S^2/2 block FLOPs (the paper's map)
      'bb'     — bounding box, S^2 block FLOPs + mask (baseline)
    """
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    dv = v.shape[-1]  # MLA uses v_head_dim != qk head dim
    g = hq // hkv
    if scale is None:
        scale = d**-0.5
    chunk = _best_chunk(s, chunk)
    nq = s // chunk
    if schedule == "folded" and (nq < 2 or nq % 2):
        schedule = "bb"

    qt = q.reshape(b, hkv, g, nq, chunk, d).astype(jnp.float32) * scale
    qt = qt.astype(q.dtype)
    kt = k.reshape(b, hkv, nq, chunk, d)
    vt = v.reshape(b, hkv, nq, chunk, dv)
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)

    if schedule == "bb":
        # scan over kv tiles; every step touches ALL q tiles (masked) —
        # the bounding-box parallel space of the paper's Fig. 2.
        def step(carry, j):
            m, l, acc = carry
            kb = kt[:, :, j]
            vb = vt[:, :, j]
            sc = _gqa_scores(qt, kb)  # (B,Hkv,G,nq,bq,bk)
            qtile = jnp.arange(nq)
            causal = (qtile[:, None, None] * chunk + row[None]) >= (
                j * chunk + col[None]
            )
            sc = jnp.where(causal[None, None, None], sc, NEG_INF)
            m_new = jnp.maximum(m, sc.max(-1))
            alpha = jnp.exp(m - m_new)
            pr = jnp.exp(sc - m_new[..., None])
            l_new = l * alpha + pr.sum(-1)
            acc_new = acc * alpha[..., None] + _gqa_out(pr, vb)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hkv, g, nq, chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, nq, chunk), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, nq, chunk, dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), jnp.arange(nq))
        out = acc / jnp.where(l == 0, 1.0, l)[..., None]
        return out.reshape(b, hq, s, dv).astype(q.dtype)

    # ---- folded simplex schedule ----
    p_idx = jnp.arange(nq // 2)

    def step(carry, j):
        m, l, acc, out = carry
        second = j > p_idx
        qsel = jnp.where(second, nq - 1 - p_idx, p_idx)  # (P,)
        ksel = jnp.where(second, j - p_idx - 1, j)
        start = (j == 0) | (j == p_idx + 1)
        last = (j == p_idx) | (j == nq)
        qb = jnp.take(qt, qsel, axis=3)  # (B,Hkv,G,P,bq,D)
        kb = jnp.take(kt, ksel, axis=2)  # (B,Hkv,P,bk,D)
        vb = jnp.take(vt, ksel, axis=2)
        # reset running state at segment starts
        m = jnp.where(start[:, None], jnp.full_like(m, NEG_INF), m)
        l = jnp.where(start[:, None], 0.0, l)
        acc = jnp.where(start[:, None, None], 0.0, acc)
        sc = _gqa_scores(qb, kb)  # (B,Hkv,G,P,bq,bk)
        on_diag = qsel == ksel
        mask = on_diag[:, None, None] & (col[None] > row[None])
        sc = jnp.where(mask[None, None, None], NEG_INF, sc)
        m_new = jnp.maximum(m, sc.max(-1))
        alpha = jnp.exp(m - m_new)
        pr = jnp.exp(sc - m_new[..., None])
        l_new = l * alpha + pr.sum(-1)
        acc_new = acc * alpha[..., None] + _gqa_out(pr, vb)
        # flush finished q tiles into the (nq+1)-padded output; slot -> its
        # q tile when finishing, else the trash tile nq.
        dest = jnp.where(last, qsel, nq)
        norm = acc_new / jnp.where(l_new == 0, 1.0, l_new)[..., None]
        out = out.at[:, :, :, dest].set(
            norm, mode="drop", unique_indices=False
        )
        return (m_new, l_new, acc_new, out), None

    P = nq // 2
    m0 = jnp.full((b, hkv, g, P, chunk), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, P, chunk), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, P, chunk, dv), jnp.float32)
    o0 = jnp.zeros((b, hkv, g, nq + 1, chunk, dv), jnp.float32)
    (_, _, _, out), _ = jax.lax.scan(step, (m0, l0, a0, o0), jnp.arange(nq + 1))
    out = out[:, :, :, :nq]
    return out.reshape(b, hq, s, dv).astype(q.dtype)


def full_attention(q, k, v, *, chunk: int = 512, scale=None, mask=None):
    """Bidirectional (encoder / cross) attention, chunked over kv."""
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = hq // hkv
    if scale is None:
        scale = d**-0.5
    chunk = _best_chunk(sk, chunk)
    nk = sk // chunk
    qg = (q.astype(jnp.float32) * scale).astype(q.dtype)
    qg = qg.reshape(b, hkv, g, sq, d)
    kt = k.reshape(b, hkv, nk, chunk, d)
    vt = v.reshape(b, hkv, nk, chunk, dv)

    def step(carry, j):
        m, l, acc = carry
        sc = _gqa_scores(qg, kt[:, :, j])  # (B,Hkv,G,sq,bk)
        if mask is not None:
            mb = jax.lax.dynamic_slice_in_dim(mask, j * chunk, chunk, axis=-1)
            sc = jnp.where(mb[:, None, None], sc, NEG_INF)
        m_new = jnp.maximum(m, sc.max(-1))
        alpha = jnp.exp(m - m_new)
        pr = jnp.exp(sc - m_new[..., None])
        l_new = l * alpha + pr.sum(-1)
        acc_new = acc * alpha[..., None] + _gqa_out(pr, vt[:, :, j])
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, sq, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), jnp.arange(nk))
    out = acc / jnp.where(l == 0, 1.0, l)[..., None]
    return out.reshape(b, hq, sq, dv).astype(q.dtype)


def simplex_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    impl: str = "auto",
    chunk: int = 512,
    schedule: str = "folded",
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Causal attention through the backend-aware dispatch (DESIGN.md §8).

    The production prefill/training entry: picks between the
    folded-simplex Pallas flash kernel
    (``kernels.flash_attention.flash_attention``) and the portable
    chunked XLA realization (``chunked_causal_attention``), resolving
    ``impl='auto'`` through the cached
    ``autotune.choose_attn_impl(seq, heads, head_dim, backend)``
    decision (roofline prior, measured ATTN rows when available, with
    the interpret step cap as a safety valve).

    Only ``impl='auto'`` may run the chunked path in place of the
    kernel, and it says so with a ``UserWarning`` naming the reason:
    MLA-style ``v_head_dim != qk head_dim`` (the flash kernel assumes
    square tiles over one head dim), ragged GQA group sizes, no tile
    that divides the sequence, or a decision for chunked.  A forced
    ``'flash*'`` impl that the kernel cannot map raises instead.  The
    decode strip stays on ``decode_attention`` — a 1-token query has
    no simplex to fold (see the §8 decode-exclusion rationale).

    Args:
        q: Queries (B, Hq, S, D).
        k: Keys (B, Hkv, S, D); Hq must be a multiple of Hkv (GQA).
        v: Values (B, Hkv, S, Dv).
        impl: 'auto' | 'flash' | 'chunked', plus the benchmark knobs
            'flash-folded' / 'flash-bb' forcing the kernel schedule.
        chunk: Chunk size for the XLA path.
        schedule: 'folded' | 'bb' for the XLA path.
        scale: Score scale; None = D**-0.5.
        interpret: Pallas interpret override; None = policy default.

    Returns:
        Attention output, (B, Hq, S, Dv), in q's dtype.

    Raises:
        ValueError: Unknown ``impl``, or a forced flash impl on a shape
            the kernel cannot map.
    """
    if impl not in ("auto", "flash", "chunked", "flash-folded", "flash-bb"):
        raise ValueError(
            "impl must be 'auto', 'flash', 'chunked', 'flash-folded' or "
            f"'flash-bb'; got {impl!r}"
        )
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    if impl != "chunked":
        dec = None
        if v.shape[-1] != d:
            why = f"value head dim {v.shape[-1]} != qk head dim {d}"
        elif hkv == 0 or hq % hkv:
            why = f"{hq} query heads do not group over {hkv} kv heads"
        else:
            from repro.autotune import choose_attn_impl

            dec = choose_attn_impl(s, hq, d)
            why = None
            if dec.block_q == 0:
                why = f"no flash tile divides s={s} at head dim {d}"
            elif impl == "auto" and dec.impl != "flash":
                why = f"the decision is {dec.impl} (source {dec.source})"
        if why is None:
            from repro.kernels.flash_attention import flash_attention

            if "-" in impl:
                kind = impl.split("-", 1)[1]
            else:
                kind = dec.kind if dec.kind in ("folded", "bb") else "folded"
            return flash_attention(
                q, k, v, kind=kind, block_q=dec.block_q,
                block_kv=dec.block_q, scale=scale, interpret=interpret,
            )
        if impl != "auto":
            raise ValueError(
                f"attention impl {impl!r} forces the flash kernel, which "
                f"cannot map q{tuple(q.shape)} k{tuple(k.shape)} "
                f"v{tuple(v.shape)}: {why}"
            )
        warnings.warn(
            f"simplex_attention: chunked XLA attention for "
            f"q{tuple(q.shape)}: {why}",
            stacklevel=2,
        )
    return chunked_causal_attention(
        q, k, v, chunk=chunk, schedule=schedule, scale=scale
    )


def sharded_causal_attention(q, k, v, cfg, mesh):
    """Causal attention under explicit shard_map: q heads shard over
    'model', KV replicated and sliced locally to the group the shard's
    q heads need — so the folded schedule's tile gathers/scatters are
    *local* and GSPMD inserts zero collectives inside the scan (the
    §Perf fix for the per-step resharding pathology; see EXPERIMENTS.md
    §Perf iteration A2).

    Single-device (mesh-less) calls — the serving/training hot path on
    one chip — route through ``simplex_attention`` so prefill launches
    the folded flash kernel by default; shard_map bodies keep the
    chunked XLA realization (Pallas under GSPMD is out of the dispatch
    contract — DESIGN.md §8)."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    if mesh is None or "model" not in mesh.axis_names:
        return simplex_attention(
            q, k, v,
            impl=getattr(cfg, "attention_impl", "auto"),
            chunk=cfg.attention_chunk,
            schedule=cfg.attention_schedule,
        )
    if getattr(cfg, "tp_size", 16) <= 1:
        # no TP: attention is batch-local; shard_map over ALL axes on
        # batch keeps the folded tile walk collective-free.
        import numpy as np
        from jax.sharding import PartitionSpec as P

        axes = tuple(mesh.axis_names)
        nsh = int(np.prod([mesh.shape[a] for a in axes]))
        if b % nsh:
            return chunked_causal_attention(
                q, k, v, chunk=cfg.attention_chunk,
                schedule=cfg.attention_schedule,
            )
        f = jax.shard_map(
            lambda ql, kl, vl: chunked_causal_attention(
                ql, kl, vl, chunk=cfg.attention_chunk,
                schedule=cfg.attention_schedule,
            ),
            mesh=mesh,
            in_specs=(P(axes), P(axes), P(axes)),
            out_specs=P(axes),
            check_vma=False,
        )
        return f(q, k, v)
    msize = mesh.shape["model"]
    hq_loc = hq // msize if hq % msize == 0 else 0
    aligned = hq_loc > 0 and (
        hq_loc % group == 0 or (group % hq_loc == 0)
    )
    if not aligned:
        return chunked_causal_attention(
            q, k, v, chunk=cfg.attention_chunk, schedule=cfg.attention_schedule
        )
    import numpy as np
    from jax.sharding import PartitionSpec as P

    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_size = int(np.prod([mesh.shape[a] for a in dp]))
    bspec = dp if b % dp_size == 0 else None
    kv_needed = max(hq_loc // group, 1)

    def body(ql, kl, vl):
        m = jax.lax.axis_index("model")
        kv_start = (m * hq_loc) // group
        kls = jax.lax.dynamic_slice_in_dim(kl, kv_start, kv_needed, axis=1)
        vls = jax.lax.dynamic_slice_in_dim(vl, kv_start, kv_needed, axis=1)
        return chunked_causal_attention(
            ql, kls, vls, chunk=cfg.attention_chunk,
            schedule=cfg.attention_schedule,
        )

    f = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(bspec, "model", None, None),
            P(bspec, None, None, None),
            P(bspec, None, None, None),
        ),
        out_specs=P(bspec, "model", None, None),
        check_vma=False,
    )
    return f(q, k, v)


def decode_attention(q, k_cache, v_cache, k_new, v_new, *, scale=None):
    """One-token attention against a full cache plus the new token.

    q: (B, Hq, 1, D); caches: (B, Hkv, S, D); k/v_new: (B, Hkv, 1, D).
    """
    b, hq, _, d = q.shape
    hkv = k_cache.shape[1]
    g = hq // hkv
    if scale is None:
        scale = d**-0.5
    qg = (q.astype(jnp.float32) * scale).astype(q.dtype).reshape(b, hkv, g, 1, d)
    sc_c = _gqa_scores(qg, k_cache)  # (B,Hkv,G,1,S)
    sc_n = _gqa_scores(qg, k_new)  # (B,Hkv,G,1,1)
    m = jnp.maximum(sc_c.max(-1), sc_n.max(-1))[..., None]
    pc = jnp.exp(sc_c - m)
    pn = jnp.exp(sc_n - m)
    l = pc.sum(-1, keepdims=True) + pn.sum(-1, keepdims=True)
    out = (_gqa_out(pc, v_cache) + _gqa_out(pn, v_new)) / l.astype(jnp.float32)
    return out.reshape(b, hq, 1, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention layer
# ---------------------------------------------------------------------------


def attn_init(key, cfg, dtype):
    """GQA projection params: wq (D, Hq*hd), wk/wv (D, Hkv*hd), wo."""
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": dense_init(k1, (d, hq * hd), dtype),
        "wk": dense_init(k2, (d, hkv * hd), dtype),
        "wv": dense_init(k3, (d, hkv * hd), dtype),
        "wo": dense_init(k4, (hq * hd, d), dtype),
    }


def attn_apply(
    p,
    cfg,
    x,
    positions,
    *,
    cache: Optional[Tuple[jax.Array, jax.Array]] = None,
    mode: str = "train",
    cross_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
    bidirectional: bool = False,
    positions3=None,
    mesh=None,
):
    """Returns (out, new_cache).  Modes:
    train/prefill — full-sequence causal (or bidirectional) attention;
    decode        — x is (B, 1, D) attending to ``cache``.
    ``cross_kv``  — use the given encoder K/V (cross-attention).
    """
    b, s, d = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    dt = x.dtype
    with jax.named_scope("layer.attn_qkv"):
        q = jnp.dot(x, p["wq"].astype(dt)).reshape(b, s, hq, hd)
        q = q.transpose(0, 2, 1, 3)
        if cross_kv is None:
            k = jnp.dot(x, p["wk"].astype(dt)).reshape(b, s, hkv, hd)
            v = jnp.dot(x, p["wv"].astype(dt)).reshape(b, s, hkv, hd)
            k = k.transpose(0, 2, 1, 3)
            v = v.transpose(0, 2, 1, 3)
    if cross_kv is None:
        with jax.named_scope("layer.rope"):
            if cfg.mrope_sections is not None and positions3 is not None:
                from .layers import mrope

                q = mrope(q, positions3, cfg.mrope_sections, cfg.rope_theta)
                k = mrope(k, positions3, cfg.mrope_sections, cfg.rope_theta)
            else:
                q = rope(q, positions, cfg.rope_theta)
                k = rope(k, positions, cfg.rope_theta)
    else:
        k, v = cross_kv

    new_cache = None
    with jax.named_scope("layer.attn_core"):
        if mode == "decode" and cross_kv is None:
            kc, vc = cache
            o = decode_attention(q, kc, vc, k, v)
        elif bidirectional or cross_kv is not None:
            o = full_attention(q, k, v, chunk=cfg.attention_chunk)
        else:
            o = sharded_causal_attention(q, k, v, cfg, mesh)
    with jax.named_scope("layer.kv_cache"):
        if mode == "decode" and cross_kv is None:
            new_cache = (kc, vc, k, v)  # caller appends (ring/position update)
        elif mode == "prefill" and cross_kv is None:
            new_cache = (k, v)
    with jax.named_scope("layer.attn_out"):
        o = o.transpose(0, 2, 1, 3).reshape(b, s, hq * hd)
        return jnp.dot(o, p["wo"].astype(dt)), new_cache


def init_kv_cache(cfg, batch, seq, dtype):
    """Zeroed decode K/V cache pair, each (batch, Hkv, seq, hd)."""
    hkv, hd = cfg.n_kv_heads, cfg.hd
    return (
        jnp.zeros((batch, hkv, seq, hd), dtype),
        jnp.zeros((batch, hkv, seq, hd), dtype),
    )
