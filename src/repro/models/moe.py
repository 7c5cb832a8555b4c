"""Mixture-of-Experts: shared + routed top-k with capacity-based dispatch.

Dispatch is GShard/Switch-style — position-in-expert via a cumulative
sum, capacity-dropped scatter into an (E, C, d) buffer, batched expert
SwiGLU, gather-combine — fully differentiable, no (T, E, C) one-hot
einsum (the scatter/gather forms keep memory at O(T*k*d)).

Distribution (DESIGN.md §4): under ``impl='tp'`` expert ff dims shard
over the 'model' axis via GSPMD like any dense layer; routing/dispatch
runs inside ``shard_map`` over the data axes so capacity is *local* to
each data shard (the GShard "group" semantics real systems use), with a
single per-token psum over 'model' after combine.  ``impl='ep'`` places
whole experts on 'model' shards and exchanges tokens with all-to-all —
the collective-trade alternative measured in EXPERIMENTS.md §Perf.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import dense_init, swiglu, swiglu_init

__all__ = ["moe_init", "moe_apply"]


def moe_init(key, cfg, dtype):
    mc = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, mc.n_experts), jnp.float32, scale=d**-0.5),
        "w1": dense_init(ks[1], (mc.n_experts, d, mc.expert_ff), dtype),
        "w3": dense_init(ks[2], (mc.n_experts, d, mc.expert_ff), dtype),
        "w2": dense_init(ks[3], (mc.n_experts, mc.expert_ff, d), dtype),
    }
    if mc.n_shared:
        shared_ff = mc.shared_ff or mc.n_shared * mc.expert_ff
        p["shared"] = swiglu_init(ks[4], d, shared_ff, dtype)
    return p


def _route(logits, mc):
    """(T, E) router logits -> (gates (T,k), idx (T,k), probs (T,E))."""
    if mc.router == "sigmoid":  # DeepSeek-V3 style
        scores = jax.nn.sigmoid(logits)
        gates, idx = jax.lax.top_k(scores, mc.top_k)
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-9)
        probs = scores / (scores.sum(-1, keepdims=True) + 1e-9)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, mc.top_k)
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-9)
    return gates, idx, probs


def _dispatch_compute_combine(x2, gates, idx, probs, p, mc, dt, psum_axis):
    """Local-capacity MoE core.  x2: (T, d)."""
    t, d = x2.shape
    e, k = mc.n_experts, mc.top_k
    cap = int(math.ceil(t * k / e * mc.capacity_factor))
    cap = max(cap, 4)
    # position of each (token, slot) within its expert, GShard priority:
    # slot-major then token order.
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)  # (T, k, E)
    flat = onehot.transpose(1, 0, 2).reshape(k * t, e)
    pos_flat = jnp.cumsum(flat, axis=0) - 1  # (k*T, E)
    pos = (
        jnp.take_along_axis(
            pos_flat.reshape(k, t, e),
            idx.transpose(1, 0)[..., None],
            axis=2,
        )[..., 0]
    ).transpose(1, 0)  # (T, k)
    keep = pos < cap
    slot = jnp.where(keep, idx * cap + pos, e * cap)  # drop -> OOB
    # scatter tokens into the (E*C, d) buffer (duplicated per chosen slot)
    buf = jnp.zeros((e * cap, d), dt)
    xk = jnp.broadcast_to(x2[:, None, :], (t, k, d)).reshape(t * k, d)
    buf = buf.at[slot.reshape(-1)].add(xk, mode="drop")
    buf = buf.reshape(e, cap, d)
    # batched expert SwiGLU
    h = jnp.einsum("ecd,edf->ecf", buf, p["w1"].astype(dt))
    u = jnp.einsum("ecd,edf->ecf", buf, p["w3"].astype(dt))
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, p["w2"].astype(dt))
    # gather-combine FIRST, then reduce the (T, d) partial over 'model' —
    # T*d bytes per layer instead of E*C*d (~k*cf x more), see §Perf.
    yf = y.reshape(e * cap, d)
    out_k = jnp.take(yf, jnp.minimum(slot, e * cap - 1).reshape(-1), axis=0)
    out_k = out_k.reshape(t, k, d) * (gates * keep).astype(dt)[..., None]
    out = out_k.sum(1)
    if psum_axis is not None:
        out = jax.lax.psum(out, psum_axis)
    # aux load-balance loss (Switch): E * sum_e f_e * p_e
    frac_tokens = jnp.mean(
        (jax.nn.one_hot(idx, e, dtype=jnp.float32) * keep[..., None]).sum(1), axis=0
    )
    frac_probs = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac_tokens * frac_probs)
    return out, aux


def moe_apply(p, cfg, x, mesh=None):
    """x: (B, S, d) -> (out, aux_loss).  ``mesh``: optional jax Mesh whose
    ('pod','data') axes shard tokens and 'model' shards expert ff."""
    mc = cfg.moe
    b, s, d = x.shape
    dt = x.dtype

    def local(xl, router, w1, w3, w2, psum_axis=None):
        t = xl.shape[0] * xl.shape[1]
        x2 = xl.reshape(t, d)
        logits = jnp.dot(x2.astype(jnp.float32), router)
        gates, idx, probs = _route(logits, mc)
        sub = {"w1": w1, "w3": w3, "w2": w2}
        out, aux = _dispatch_compute_combine(
            x2, gates, idx, probs, sub, mc, dt, psum_axis
        )
        return out.reshape(xl.shape), aux

    if mesh is None:
        out, aux = local(x, p["router"], p["w1"], p["w3"], p["w2"])
    elif (
        (getattr(cfg, "moe_impl", "") or mc.impl) == "ep"
        and getattr(cfg, "tp_size", 16) > 1
        and mc.n_experts % mesh.shape.get("model", 1) == 0
    ):
        out, aux = _moe_ep(p, cfg, x, mesh)
    else:
        import numpy as np

        tp = getattr(cfg, "tp_size", 16) > 1
        dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        if not tp:
            dp = dp + ("model",)
        dp_size = int(np.prod([mesh.shape[a] for a in dp]))
        bspec = dp if b % dp_size == 0 else None  # batch-1 decode: replicate
        ff_ok = tp and mc.expert_ff % mesh.shape["model"] == 0
        ffspec = "model" if ff_ok else None
        psum_ax = "model" if ff_ok else None

        def body(xl, r, w1, w3, w2):
            o, a = local(xl, r, w1, w3, w2, psum_ax)
            return o, jax.lax.pmean(a, dp)

        f = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(
                P(bspec, None, None),
                P(None, None),
                P(None, None, ffspec),
                P(None, None, ffspec),
                P(None, ffspec, None),
            ),
            out_specs=(P(bspec, None, None), P()),
            check_vma=False,
        )
        out, aux = f(x, p["router"], p["w1"], p["w3"], p["w2"])

    if mc.n_shared:
        out = out + swiglu(p["shared"], x)
    return out, aux * mc.aux_loss_weight


def _moe_ep(p, cfg, x, mesh):
    """Expert parallelism: experts live on 'model' shards; tokens move to
    their experts with all-to-all and return after compute (GShard).

    vs TP-experts: every device computes only E/|model| experts, so the
    expert-weight HBM/gather traffic divides by |model| (the MoE lever of
    EXPERIMENTS.md §Perf C-series); the price is two all-to-alls of
    ~top_k*tokens*d per layer instead of one token psum.
    """
    import numpy as np

    mc = cfg.moe
    b, s, d = x.shape
    dt = x.dtype
    msize = mesh.shape["model"]
    e_loc = mc.n_experts // msize
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_size = int(np.prod([mesh.shape[a] for a in dp]))
    bspec = dp if b % dp_size == 0 else None

    def body(xl, router, w1, w3, w2):
        # xl: (B_loc, S, d) — replicated over 'model' (bspec covers dp only)
        t = xl.shape[0] * xl.shape[1]
        x2 = xl.reshape(t, d)
        logits = jnp.dot(x2.astype(jnp.float32), router)
        gates, idx, probs = _route(logits, mc)
        # capacity per expert for THIS shard's tokens
        cap = max(int(math.ceil(t * mc.top_k / mc.n_experts
                                * mc.capacity_factor)), 4)
        onehot = jax.nn.one_hot(idx, mc.n_experts, dtype=jnp.int32)
        flat = onehot.transpose(1, 0, 2).reshape(mc.top_k * t, mc.n_experts)
        pos_flat = jnp.cumsum(flat, axis=0) - 1
        pos = jnp.take_along_axis(
            pos_flat.reshape(mc.top_k, t, mc.n_experts),
            idx.transpose(1, 0)[..., None], axis=2,
        )[..., 0].transpose(1, 0)
        keep = pos < cap
        slot = jnp.where(keep, idx * cap + pos, mc.n_experts * cap)
        buf = jnp.zeros((mc.n_experts * cap, d), dt)
        xk = jnp.broadcast_to(x2[:, None, :], (t, mc.top_k, d)).reshape(-1, d)
        buf = buf.at[slot.reshape(-1)].add(xk, mode="drop")
        # (E, cap, d) -> exchange: each model shard keeps its E/msize
        # experts' buffers from EVERY model shard.
        buf = buf.reshape(msize, e_loc, cap, d)
        recv = jax.lax.all_to_all(
            buf, "model", split_axis=0, concat_axis=0, tiled=False
        )  # (msize peers, e_loc, cap, d): peer j's tokens for my experts
        recv = recv.transpose(1, 0, 2, 3).reshape(e_loc, msize * cap, d)
        h = jnp.einsum("ecd,edf->ecf", recv, w1.astype(dt))
        u = jnp.einsum("ecd,edf->ecf", recv, w3.astype(dt))
        y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, w2.astype(dt))
        y = y.reshape(e_loc, msize, cap, d).transpose(1, 0, 2, 3)
        back = jax.lax.all_to_all(
            y, "model", split_axis=0, concat_axis=0, tiled=False
        ).reshape(mc.n_experts * cap, d)  # my tokens' results, expert-major
        out_k = jnp.take(back, jnp.minimum(slot, mc.n_experts * cap - 1)
                         .reshape(-1), axis=0)
        out_k = out_k.reshape(t, mc.top_k, d) * (gates * keep).astype(dt)[..., None]
        out = out_k.sum(1).reshape(xl.shape)
        frac_tokens = jnp.mean(
            (onehot.astype(jnp.float32) * keep[..., None]).sum(1), axis=0
        )
        aux = mc.n_experts * jnp.sum(frac_tokens * jnp.mean(probs, axis=0))
        return out, jax.lax.pmean(aux, dp)

    f = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(bspec, None, None),
            P(None, None),
            P("model", None, None),
            P("model", None, None),
            P("model", None, None),
        ),
        out_specs=(P(bspec, None, None), P()),
        check_vma=False,
    )
    return f(x, p["router"], p["w1"], p["w3"], p["w2"])
