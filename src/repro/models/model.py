"""Model facade: init / loss / prefill / decode / input_specs.

One class drives all 10 assigned architectures from their ArchConfig:
decoder-only LMs (dense, MoE, MLA, hybrid, ssm), the VLM stub (patch
embeddings prepended, M-RoPE positions), and the audio encoder-decoder
(frame-embedding encoder + cross-attending decoder).  The dry-run lowers
``train_step`` / ``prefill_step`` / ``serve_step`` built from these.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, LayerSpec, ShapeCfg

from .layers import embed, embed_init, rmsnorm, rmsnorm_init, dense_init
from .transformer import (
    block_apply,
    block_init,
    init_block_cache,
    stack_apply,
    stack_init,
)

__all__ = ["Model"]


class Model:
    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.specs = tuple(cfg.period)
        self.prefix_specs = tuple(cfg.prefix_spec)
        self.is_encdec = cfg.encoder_layers > 0
        self.pdtype = jnp.dtype(cfg.param_dtype)
        self.adtype = jnp.dtype(cfg.act_dtype)

    # ------------------------------------------------------------------ init

    def init(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        ks = jax.random.split(key, 8)
        params: Dict[str, Any] = {
            "embed": embed_init(ks[0], cfg.vocab, cfg.d_model, self.pdtype),
            "final_norm": rmsnorm_init(cfg.d_model, self.pdtype),
            "stack": stack_init(
                ks[1], cfg, self.specs, cfg.n_periods, self.pdtype,
                cross=self.is_encdec,
            ),
        }
        if self.prefix_specs:
            params["prefix"] = {
                f"p{i}": block_init(
                    jax.random.fold_in(ks[2], i), cfg, s, self.pdtype,
                    cross=self.is_encdec,
                )
                for i, s in enumerate(self.prefix_specs)
            }
        if not cfg.tie_embeddings:
            params["unembed"] = dense_init(
                ks[3], (cfg.d_model, cfg.vocab), self.pdtype
            )
        if self.is_encdec:
            params["encoder"] = {
                "stack": stack_init(
                    ks[4], cfg, (LayerSpec("attn", "dense"),),
                    cfg.encoder_layers, self.pdtype,
                ),
                "final_norm": rmsnorm_init(cfg.d_model, self.pdtype),
            }
        if cfg.mtp:
            params["mtp"] = {
                "proj": dense_init(ks[5], (2 * cfg.d_model, cfg.d_model), self.pdtype),
                "block": block_init(ks[6], cfg, LayerSpec("attn", "dense"), self.pdtype),
                "norm": rmsnorm_init(cfg.d_model, self.pdtype),
            }
        return params

    # ------------------------------------------------------------ embeddings

    def _embed_inputs(self, params, batch):
        """Returns (embeds (B,S,d), positions (B,S), positions3 or None)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        with jax.named_scope("model.embed"):
            x = embed(params["embed"], tokens, self.adtype)
        b = tokens.shape[0]
        if cfg.n_patches and "patches" in batch:
            patches = batch["patches"].astype(self.adtype)  # (B,P,d)
            x = jnp.concatenate([patches, x], axis=1)
            s = x.shape[1]
            pos3 = self._mrope_positions(b, s)
            positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            return x, positions, pos3
        s = x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        return x, positions, None

    def _mrope_positions(self, b, s):
        cfg = self.cfg
        p = cfg.n_patches
        side = int(math.isqrt(p)) or 1
        t = jnp.concatenate([jnp.zeros((p,), jnp.int32), jnp.arange(s - p) + 1])
        hh = jnp.concatenate(
            [jnp.arange(p) // side, jnp.arange(s - p) + 1 + (side - 1)]
        )
        ww = jnp.concatenate(
            [jnp.arange(p) % side, jnp.arange(s - p) + 1 + (side - 1)]
        )
        pos3 = jnp.stack([t, hh, ww], axis=-1).astype(jnp.int32)  # (S,3)
        return jnp.broadcast_to(pos3[None], (b, s, 3))

    # --------------------------------------------------------------- forward

    def _backbone(self, params, x, positions, *, caches=None, mode="train",
                  mesh=None, enc_out=None, positions3=None):
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        new_caches: Dict[str, Any] = {}
        if self.prefix_specs:
            pc = {}
            for i, spec in enumerate(self.prefix_specs):
                c_i = caches["prefix"][f"p{i}"] if caches else None
                cross_cache = (
                    c_i.get("cross") if (c_i and mode == "decode") else None
                )
                x, nc, a = block_apply(
                    params["prefix"][f"p{i}"], cfg, spec, x, positions,
                    cache=c_i, mode=mode, mesh=mesh, enc_out=enc_out,
                    cross_cache=cross_cache, positions3=positions3,
                )
                if mode == "decode" and c_i and "cross" in c_i:
                    nc["cross"] = c_i["cross"]
                pc[f"p{i}"] = nc
                aux = aux + a
            new_caches["prefix"] = pc
        x, sc, a = stack_apply(
            params["stack"], cfg, self.specs, cfg.n_periods, x, positions,
            caches=caches["stack"] if caches else None, mode=mode, mesh=mesh,
            enc_out=enc_out, positions3=positions3,
        )
        new_caches["stack"] = sc
        aux = aux + a
        with jax.named_scope("model.lm_head"):
            x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        return x, new_caches, aux

    def _encode(self, params, src_embeds):
        cfg = self.cfg
        b, s, _ = src_embeds.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        x, _, _ = stack_apply(
            params["encoder"]["stack"], cfg, (LayerSpec("attn", "dense"),),
            cfg.encoder_layers, src_embeds.astype(self.adtype), positions,
            mode="train", bidirectional=True,
        )
        return rmsnorm(params["encoder"]["final_norm"], x, cfg.norm_eps)

    def _logits(self, params, x):
        cfg = self.cfg
        with jax.named_scope("model.lm_head"):
            w = (
                params["embed"]["e"].T if cfg.tie_embeddings
                else params["unembed"]
            ).astype(self.adtype)
            return jnp.dot(x, w)

    # ------------------------------------------------------------------ loss

    def loss(self, params, batch, mesh=None):
        """Next-token CE (+ MoE aux + MTP).  batch['tokens']: (B, S+1)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        inp = {**batch, "tokens": tokens[:, :-1]}
        labels = tokens[:, 1:]
        x, positions, pos3 = self._embed_inputs(params, inp)
        enc_out = None
        if self.is_encdec:
            enc_out = self._encode(params, batch["src_embeds"])
        h, _, aux = self._backbone(
            params, x, positions, mode="train", mesh=mesh, enc_out=enc_out,
            positions3=pos3,
        )
        n_text = labels.shape[1]
        h_text = h[:, -n_text:]  # skip patch positions (vlm)
        logits = self._logits(params, h_text)
        ce = _cross_entropy(logits, labels)
        total = ce + aux
        if cfg.mtp:
            total = total + 0.3 * self._mtp_loss(params, h_text, tokens, mesh)
        return total, {"ce": ce, "aux": aux}

    def _mtp_loss(self, params, h, tokens, mesh):
        """DeepSeek-V3 multi-token prediction: depth-1 extra head that
        predicts token t+2 from [h_t ; emb(token_{t+1})]."""
        cfg = self.cfg
        emb_next = embed(params["embed"], tokens[:, 1:-1], self.adtype)
        h_in = jnp.concatenate([h[:, :-1], emb_next], axis=-1)
        x = jnp.dot(h_in, params["mtp"]["proj"].astype(self.adtype))
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        x, _, _ = block_apply(
            params["mtp"]["block"], cfg, LayerSpec("attn", "dense"), x,
            positions, mode="train", mesh=mesh,
        )
        x = rmsnorm(params["mtp"]["norm"], x, cfg.norm_eps)
        return _cross_entropy(self._logits(params, x), tokens[:, 2:])

    # ------------------------------------------------------- prefill / decode

    def prefill(self, params, batch, mesh=None):
        """Full-sequence forward filling caches; returns (last_logits, caches)."""
        inp = dict(batch)
        x, positions, pos3 = self._embed_inputs(params, inp)
        enc_out = self._encode(params, batch["src_embeds"]) if self.is_encdec else None
        h, caches, _ = self._backbone(
            params, x, positions, mode="prefill", mesh=mesh, enc_out=enc_out,
            positions3=pos3,
        )
        return self._logits(params, h[:, -1:]), caches

    def decode(self, params, caches, batch, mesh=None):
        """One token against full caches.  batch['tokens']: (B, 1);
        batch['pos']: (B,) absolute position of the new token."""
        with jax.named_scope("model.embed"):
            x = embed(params["embed"], batch["tokens"], self.adtype)
        b = x.shape[0]
        positions = batch["pos"][:, None]
        pos3 = None
        if self.cfg.mrope_sections is not None:
            pos3 = jnp.broadcast_to(
                positions[..., None], (b, 1, 3)
            ).astype(jnp.int32)
        h, new_caches, _ = self._backbone(
            params, x, positions, caches=caches, mode="decode", mesh=mesh,
            positions3=pos3,
        )
        return self._logits(params, h), new_caches

    # ----------------------------------------------------------------- caches

    def init_cache(self, batch, seq, dtype=None):
        cfg = self.cfg
        dtype = dtype or self.adtype
        caches: Dict[str, Any] = {}
        if self.prefix_specs:
            caches["prefix"] = {
                f"p{i}": init_block_cache(
                    cfg, s, batch, seq, dtype, cross=self.is_encdec
                )
                for i, s in enumerate(self.prefix_specs)
            }

        def one_period():
            return {
                f"l{i}": init_block_cache(
                    cfg, s, batch, seq, dtype, cross=self.is_encdec
                )
                for i, s in enumerate(self.specs)
            }

        p0 = one_period()
        caches["stack"] = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (cfg.n_periods,) + x.shape), p0
        )
        return caches

    # ------------------------------------------------------------ input specs

    def input_specs(self, shape: ShapeCfg) -> Dict[str, Any]:
        """ShapeDtypeStructs for every model input of the given cell —
        weak-type-correct, shardable, no device allocation."""
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        sds = jax.ShapeDtypeStruct
        if shape.mode == "train":
            batch: Dict[str, Any] = {"tokens": sds((b, s + 1), i32)}
        elif shape.mode == "prefill":
            batch = {"tokens": sds((b, s), i32)}
        else:  # decode
            batch = {"tokens": sds((b, 1), i32), "pos": sds((b,), i32)}
        if cfg.n_patches:
            if shape.mode != "decode":
                # patches replace the leading n_patches text positions
                batch["tokens"] = sds(
                    (b, batch["tokens"].shape[1] - cfg.n_patches), i32
                )
                batch["patches"] = sds(
                    (b, cfg.n_patches, cfg.d_model), jnp.bfloat16
                )
        if self.is_encdec and shape.mode != "decode":
            batch["src_embeds"] = sds((b, s, cfg.d_model), jnp.bfloat16)
        return batch


def _cross_entropy(logits, labels):
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)
