"""Causal flash attention on the 2-simplex grid — the paper's technique
made a first-class LM feature (DESIGN.md §2, serving hot path §8).

The causal score matrix is a standard 2-simplex: tiles (q_tile, kv_tile)
with kv <= q.  The bounding-box schedule (``kind='bb'``) lowers a full
(nq x nk) grid and discards the upper half with ``pl.when`` — exactly the
paper's BB baseline.  The folded schedule (``kind='folded'``) is the
zero-waste simplex walk: grid (heads, ceil(nq/2) pairs, nq+1 steps),
where pair ``p`` serves query tiles ``p`` and ``nq-1-p``:

    step j <= p:        (q, kv) = (p, j)
    step j >  p:        (q, kv) = (nq-1-p, j-p-1)

Every pair owns exactly ``nq+1`` KV tiles — constant work per grid row
(the paper's parallel-space balance, realized as the RB fold [37], which
the paper shows matches H for 2-simplices), and each query tile's KV
visits are *consecutive*, which the running-softmax recurrence requires.
An odd tile count self-pairs the middle tile (``folded_causal_pairs``'s
odd form): pair ``mid = (nq-1)/2`` has ``nq-1-mid == mid``, so its
second half-walk revisits the same (mid+1)-tile segment — the recurrence
recomputes the identical output and the final flush rewrites it, so the
fold stays branch-free at the cost of one half-row of duplicate work.
Grid steps: nq(nq+1)/2 + nq/2 (even) vs nq^2 for BB — the asymptotic 2x
of the paper's MAP test, with zero per-step predicates off the diagonal.

GQA runs inside the index maps: KV blocks are fetched per *kv head*
(``bh // group``) so grouped query heads share them with no materialized
``jnp.repeat`` — the kernel never touches a (B, Hq, S, D) KV tensor.
Optional additive ``bias`` (broadcastable over batch/head) and
``segment_ids`` (block-diagonal packing mask) ride the same block maps.

The same fold is exposed as ``core.schedule.folded_causal_pairs`` for
sequence-parallel sharding (equal triangle area per shard).

Block sizes default to TPU-native (block_q x head_dim = 128 x 128 MXU
tiles); ``autotune.attn_block_q`` picks larger square tiles where the
sequence allows, and tests sweep smaller shapes in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .engine import pallas_launch
from .policy import check_tile_alignment, compiler_params, resolve_interpret

NEG_INF = -1e30

__all__ = ["flash_attention", "flash_grid_steps", "flash_fold_pairs"]


def flash_fold_pairs(nq_tiles: int) -> int:
    """Folded-grid pair rows for ``nq_tiles`` query tiles.

    Even counts fold tile ``i`` with ``nq-1-i``; an odd count adds the
    self-paired middle tile as its own row (the ``folded_causal_pairs``
    odd form).

    Args:
        nq_tiles: Query-tile count, >= 1.

    Returns:
        ``ceil(nq_tiles / 2)`` — the folded grid's second dimension.

    Example:
        >>> flash_fold_pairs(4), flash_fold_pairs(5)
        (2, 3)
    """
    if nq_tiles < 1:
        raise ValueError(f"nq_tiles must be >= 1, got {nq_tiles}")
    return (nq_tiles + 1) // 2


def flash_grid_steps(nq_tiles: int, kind: str) -> int:
    """Grid steps the flash kernel launches for ``nq_tiles`` query tiles.

    Args:
        nq_tiles: Query-tile count, >= 1.
        kind: ``'bb'`` (full square) or ``'folded'`` (the simplex fold;
            every pair row walks ``nq+1`` steps — zero waste at even
            counts, one duplicated half-row at odd counts where the
            middle tile self-pairs).

    Returns:
        Total grid steps (excluding the batch*heads axis).

    Raises:
        ValueError: Unknown kind or non-positive tile count — the only
            genuinely unmappable inputs.

    Example:
        >>> flash_grid_steps(4, "bb"), flash_grid_steps(4, "folded")
        (16, 10)
        >>> flash_grid_steps(5, "folded")  # odd: 3 pair rows x 6 steps
        18
    """
    if nq_tiles < 1:
        raise ValueError(f"nq_tiles must be >= 1, got {nq_tiles}")
    if kind == "bb":
        return nq_tiles * nq_tiles
    if kind == "folded":
        return flash_fold_pairs(nq_tiles) * (nq_tiles + 1)
    raise ValueError(f"unknown flash schedule kind {kind!r}")


def _folded_qkv(p, j, nq):
    """Branchless fold: step (p, j) -> (q_tile, kv_tile, is_start, is_last)."""
    second = j > p
    q = jnp.where(second, nq - 1 - p, p)
    kv = jnp.where(second, j - p - 1, j)
    start = (j == 0) | (j == p + 1)
    last = (j == p) | (j == nq)
    return q, kv, start, last


def _bias_index(bias_shape, b, hq):
    """Static (div, mod) mapping from the fused bh axis into a
    broadcast bias leading axis of ``bias_b * bias_h`` slabs."""
    bias_b, bias_h = bias_shape[0], bias_shape[1]
    if bias_b not in (1, b) or bias_h not in (1, hq):
        raise ValueError(
            f"bias must broadcast over (batch={b}, heads={hq}); got "
            f"leading dims {(bias_b, bias_h)}"
        )

    def to_slab(bh):
        batch = bh // hq
        head = bh % hq
        bb = batch % bias_b if bias_b > 1 else 0
        hh = head % bias_h if bias_h > 1 else 0
        return bb * bias_h + hh

    return to_slab


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    bias: jax.Array | None = None,
    segment_ids: jax.Array | None = None,
    kind: str = "folded",
    block_q: int = 128,
    block_kv: int = 128,
    scale: float | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Causal self-attention on the simplex grid, GQA-aware.

    This is the batched-prefill/training entry the model layer launches
    (``models.attention.simplex_attention`` — DESIGN.md §8); decode
    keeps the KV-cache strip path.

    Args:
        q: Queries, ``(B, Hq, S, D)``.
        k: Keys, ``(B, Hkv, S, D)`` with ``Hq % Hkv == 0``; grouped
            query heads read each KV block straight from the kv-head
            index map (no materialized repeat).
        v: Values, same shape as ``k``.
        bias: Optional additive logit bias broadcastable to
            ``(B, Hq, S, S)`` — leading dims may each be 1.
        segment_ids: Optional ``(B, S)`` int32 packing ids; attention
            only flows within equal ids (block-diagonal mask).
        kind: ``'folded'`` (simplex fold, ~2x fewer grid steps) or
            ``'bb'`` (bounding-box baseline).
        block_q: Query tile size (clamped to S; must divide S).
        block_kv: KV tile size; the fold pairs tiles 1:1, so it must
            equal ``block_q``.
        scale: Logit scale; defaults to ``1/sqrt(D)``.
        interpret: Pallas mode; ``None`` resolves through
            ``policy.default_interpret()`` (compiled on TPU/GPU,
            interpreter on CPU).

    Returns:
        ``(B, Hq, S, D)`` attention output in ``q.dtype`` (f32 softmax
        accumulation).

    Raises:
        ValueError: Genuinely unmappable shapes — S not divisible by
            the block size, ``block_q != block_kv``, or a bias that
            cannot broadcast.  Odd query-tile counts are mapped via the
            self-pair middle fold, not rejected.
    """
    interpret = resolve_interpret(interpret)
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    assert hq % hkv == 0 and k.shape == v.shape == (b, hkv, s, d)
    block_q = min(block_q, s)
    block_kv = min(block_kv, s)
    if s % block_q or s % block_kv:
        raise ValueError(
            f"sequence length {s} must be divisible by the block size "
            f"(block_q={block_q}, block_kv={block_kv})"
        )
    if block_q != block_kv:
        raise ValueError(
            f"fold pairs q/kv tiles 1:1 (square tiles); got "
            f"block_q={block_q} != block_kv={block_kv}"
        )
    nq = s // block_q
    if scale is None:
        scale = 1.0 / (d**0.5)

    if kind == "folded" and nq == 1:
        kind = "bb"  # single tile: nothing to fold
    if kind not in ("folded", "bb"):
        raise ValueError(f"unknown flash schedule kind {kind!r}")
    seg = None if segment_ids is None else segment_ids.astype(jnp.int32)
    return _flash_core(
        kind, block_q, block_kv, float(scale), interpret, q, k, v, bias, seg
    )


def _reference_attention(q, k, v, bias, segment_ids, scale):
    """Plain-XLA causal attention — the kernel's backward-pass oracle.

    Materializes the full (B, Hq, S, S) score matrix (GQA heads via
    ``jnp.repeat``), applies the same NEG_INF causal/segment mask and
    additive bias as the kernel, and lets JAX AD differentiate it.
    Forward outputs stay on the Pallas kernel; only cotangents flow
    through here (DESIGN.md §8).
    """
    b, hq, s, d = q.shape
    g = hq // k.shape[1]
    kf = jnp.repeat(k, g, axis=1).astype(jnp.float32)
    vf = jnp.repeat(v, g, axis=1).astype(jnp.float32)
    sc = jnp.einsum("bhid,bhjd->bhij", q.astype(jnp.float32) * scale, kf)
    if bias is not None:
        sc = sc + jnp.broadcast_to(bias.astype(jnp.float32), sc.shape)
    mask = jnp.tril(jnp.ones((s, s), bool))[None, None]
    if segment_ids is not None:
        mask = mask & (
            segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
        )
    sc = jnp.where(mask, sc, NEG_INF)
    pr = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bhij,bhjd->bhid", pr, vf)
    return out.astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _flash_core(kind, block_q, block_kv, scale, interpret, q, k, v, bias, seg):
    """Differentiable core: Pallas forward, XLA-reference backward.

    The Pallas interpreter has no JVP rule, so training steps would
    fail at ``jax.grad`` without this wrapper.  The custom VJP keeps
    the simplex-scheduled kernel as the forward (the serving/training
    hot path) and routes cotangents through ``_reference_attention``
    — standard flash-attention practice until a fused backward kernel
    lands (ROADMAP follow-up).
    """
    return _flash_launch(
        kind, block_q, block_kv, scale, interpret, q, k, v, bias, seg
    )


def _flash_core_fwd(kind, block_q, block_kv, scale, interpret, q, k, v,
                    bias, seg):
    out = _flash_launch(
        kind, block_q, block_kv, scale, interpret, q, k, v, bias, seg
    )
    return out, (q, k, v, bias, seg)


def _flash_core_bwd(kind, block_q, block_kv, scale, interpret, res, g):
    q, k, v, bias, seg = res
    if bias is None:
        _, vjp = jax.vjp(
            lambda q_, k_, v_: _reference_attention(q_, k_, v_, None, seg,
                                                    scale),
            q, k, v,
        )
        dq, dk, dv = vjp(g)
        dbias = None
    else:
        _, vjp = jax.vjp(
            lambda q_, k_, v_, b_: _reference_attention(q_, k_, v_, b_, seg,
                                                        scale),
            q, k, v, bias,
        )
        dq, dk, dv, dbias = vjp(g)
    # integer segment ids carry a float0 (symbolic-zero) cotangent
    dseg = None if seg is None else np.zeros(seg.shape, jax.dtypes.float0)
    return dq, dk, dv, dbias, dseg


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def _flash_launch(kind, block_q, block_kv, scale, interpret, q, k, v,
                  bias, segment_ids):
    """Grid/spec construction + the Pallas launch (forward only)."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    nq = s // block_q
    g = hq // hkv
    if kind == "folded":
        grid = (b * hq, flash_fold_pairs(nq), nq + 1)

        def q_map(bh, p, j, *_):
            qt, _, _, _ = _folded_qkv(p, j, nq)
            return bh, qt, 0

        def kv_map(bh, p, j, *_):
            _, kt, _, _ = _folded_qkv(p, j, nq)
            return bh // g, kt, 0

        def tile_ids(p, j):
            qt, kt, start, last = _folded_qkv(p, j, nq)
            return qt, kt, start, last, jnp.bool_(True)

    else:
        grid = (b * hq, nq, nq)

        def q_map(bh, qt, kt, *_):
            return bh, qt, 0

        def kv_map(bh, qt, kt, *_):
            return bh // g, kt, 0

        def tile_ids(qt, kt):
            return qt, kt, kt == 0, kt == qt, kt <= qt

    o_map = q_map

    # ---- optional inputs: additive bias and segment-id masking ----------
    extra_in = []
    extra_specs = []
    if bias is not None:
        if bias.ndim != 4:
            raise ValueError(f"bias must be 4-D, got shape {bias.shape}")
        if bias.shape[2:] != (s, s):
            raise ValueError(
                f"bias trailing dims must be ({s}, {s}), got {bias.shape}"
            )
        to_slab = _bias_index(bias.shape, b, hq)
        bias_r = bias.reshape(-1, s, s)

        def bias_map(bh, i, j, *_):
            qt, kt, *_rest = tile_ids(i, j)
            return to_slab(bh), qt, kt

        extra_in.append(bias_r.astype(jnp.float32))
        extra_specs.append(pl.BlockSpec((1, block_q, block_kv), bias_map))
    if segment_ids is not None:
        if segment_ids.shape != (b, s):
            raise ValueError(
                f"segment_ids must be (batch, seq) = ({b}, {s}), got "
                f"{segment_ids.shape}"
            )
        seg = segment_ids.astype(jnp.int32)

        def qseg_map(bh, i, j, *_):
            qt, *_rest = tile_ids(i, j)
            return bh // hq, qt

        def kseg_map(bh, i, j, *_):
            _, kt, *_rest = tile_ids(i, j)
            return bh // hq, kt

        extra_in.extend([seg, seg])
        extra_specs.extend([
            pl.BlockSpec((1, block_q), qseg_map),
            pl.BlockSpec((1, block_kv), kseg_map),
        ])

    has_bias = bias is not None
    has_seg = segment_ids is not None

    def kernel(q_ref, k_ref, v_ref, *refs):
        i = 0
        bias_ref = seg_q_ref = seg_k_ref = None
        if has_bias:
            bias_ref = refs[i]
            i += 1
        if has_seg:
            seg_q_ref, seg_k_ref = refs[i], refs[i + 1]
            i += 2
        o_ref, m_ref, l_ref, acc_ref = refs[i : i + 4]

        qt, kt, start, last, live = tile_ids(
            pl.program_id(1), pl.program_id(2)
        )

        @pl.when(start)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(live)
        def _step():
            qb = q_ref[0].astype(jnp.float32) * scale  # (bq, d)
            kb = k_ref[0].astype(jnp.float32)  # (bk, d)
            sc = jax.lax.dot_general(
                qb, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (bq, bk)
            if has_bias:
                sc = sc + bias_ref[0]
            on_diag = qt == kt
            rq = qt * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0
            )
            ck = kt * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1
            )
            valid = jnp.logical_not(on_diag & (ck > rq))
            if has_seg:
                valid = valid & (seg_q_ref[0][:, None] == seg_k_ref[0][None, :])
            sc = jnp.where(valid, sc, NEG_INF)
            m_prev = m_ref[:, :1]  # (bq, 1)
            m_cur = jnp.max(sc, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            pr = jnp.exp(sc - m_new)  # (bq, bk)
            if has_seg:
                # a fully-masked row has m_new == NEG_INF and sc - m_new
                # == 0; zero those probabilities explicitly so packing
                # pads contribute nothing (l stays 0 -> output 0).
                pr = pr * valid.astype(jnp.float32)
            l_new = l_ref[:, :1] * alpha + jnp.sum(pr, axis=1, keepdims=True)
            acc = acc_ref[...] * alpha + jax.lax.dot_general(
                pr,
                v_ref[0].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
            l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
            acc_ref[...] = acc

        @pl.when(last)
        def _fin():
            l = l_ref[:, :1]
            l = jnp.where(l == 0.0, 1.0, l)
            o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)

    check_tile_alignment((block_q, d), interpret, what="q block")
    # q, k, v and o blocks, plus the optional bias tile and segment rows;
    # the (bq, bk) f32 scores, probabilities and mask are the temporaries
    block_bytes = 2 * (block_q + block_kv) * d * q.dtype.itemsize
    block_bytes += 4 * (block_q * block_kv * has_bias
                        + (block_q + block_kv) * has_seg)
    qr = q.reshape(b * hq, s, d)
    kr = k.reshape(b * hkv, s, d)
    vr = v.reshape(b * hkv, s, d)
    out = pallas_launch(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b * hq, s, d), q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_kv, d), kv_map),
            pl.BlockSpec((1, block_kv, d), kv_map),
            *extra_specs,
        ],
        out_specs=pl.BlockSpec((1, block_q, d), o_map),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max m
            pltpu.VMEM((block_q, 128), jnp.float32),  # running denom l
            pltpu.VMEM((block_q, d), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
        name=f"flash_{kind}",
        **compiler_params(interpret, block_bytes, 4 * block_q * block_kv),
    )(qr, kr, vr, *extra_in)
    return out.reshape(b, hq, s, d)
