"""Batched serving driver: prefill a batch of prompts, decode N tokens.

Reduced configs run on the CPU in float32 (``--smoke``); a full config
runs at its published widths with params and activations in bfloat16,
the size one TPU v5e holds for yi-6b:

  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --smoke \
      --batch 4 --prompt-len 64 --gen 32
  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b \
      --batch 4 --prompt-len 2048 --gen 16

The printed times are smoke timings of one run (compile excluded, each
ended by ``block_until_ready``), not benchmark metrics.
"""

from __future__ import annotations

import argparse
import time
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["ServeRun", "main"]


@dataclass
class ServeRun:
    """What one serve run produced, for callers that check it.

    Attributes:
        model: The ``Model`` that served.
        params: Its parameters (on the default device).
        tokens: Prompt tokens, ``(batch, prompt_len)``.
        generated: Sampled tokens, ``(batch, gen + 1)``; column 0 is
            the token fed to the first decode step.
        prefill_logits: Last-position prefill logits, ``(batch, 1, vocab)``.
        first_decode_logits: Logits of the first decode step.
        attn: The ``choose_attn_impl`` decision at the prompt shape.
        prefill_hlo: Compiled prefill program text.
        compile_s: Seconds spent compiling prefill and decode.
        prefill_s: Prefill wall time (smoke timing).
        decode_s: Wall time of all decode steps (smoke timing).
    """

    model: Any
    params: Any
    tokens: Any
    generated: np.ndarray
    prefill_logits: Any
    first_decode_logits: Any
    attn: Any
    prefill_hlo: str
    compile_s: float
    prefill_s: float
    decode_s: float


def _config(arch: str, smoke: bool):
    from repro.configs.ALL import REDUCED
    from repro.configs.base import get_config

    if smoke:
        return REDUCED[arch]().replace(
            act_dtype="float32", param_dtype="float32", remat="none"
        )
    return get_config(arch).replace(
        act_dtype="bfloat16", param_dtype="bfloat16", remat="none"
    )


def main(argv: Optional[list] = None) -> ServeRun:
    """Build the model, prefill a random batch and decode ``--gen`` steps.

    Args:
        argv: Command-line arguments (None = ``sys.argv``).

    Returns:
        The ``ServeRun`` record of this run.
    """
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config in float32 (CPU tests)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--temperature", type=float, default=0.8)
    args = ap.parse_args(argv)

    from repro.autotune import choose_attn_impl
    from repro.compile_cache import enable_compile_cache
    from repro.models.model import Model

    enable_compile_cache()
    cfg = _config(args.arch, args.smoke)
    model = Model(cfg)
    key = jax.random.PRNGKey(args.seed)
    # built inside one program: the stacked bf16 leaves are written
    # directly, with no float32 or per-layer copy held beside them.
    params = jax.jit(model.init)(key)

    b, s = args.batch, args.prompt_len
    batch = {"tokens": jax.random.randint(key, (b, s), 0, cfg.vocab)}
    if cfg.n_patches:
        batch["tokens"] = batch["tokens"][:, : s - cfg.n_patches]
        batch["patches"] = jax.random.normal(
            key, (b, cfg.n_patches, cfg.d_model), jnp.float32
        )
    if cfg.encoder_layers:
        batch["src_embeds"] = jax.random.normal(key, (b, s, cfg.d_model))

    attn = choose_attn_impl(s, cfg.n_heads, cfg.hd)
    print(
        f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads}/{cfg.n_kv_heads} heads, d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab}, {cfg.param_dtype} params, {cfg.act_dtype} acts"
    )
    print(
        f"attention decision at s={s}: {attn.impl}/{attn.kind} "
        f"(block {attn.block_q}, source {attn.source}, "
        f"config impl {cfg.attention_impl})"
    )

    step = {"tokens": jnp.zeros((b, 1), jnp.int32),
            "pos": jnp.zeros((b,), jnp.int32)}
    t0 = time.perf_counter()
    prefill = jax.jit(model.prefill).lower(params, batch).compile()
    prefill_hlo = prefill.as_text()
    caches_shape = jax.eval_shape(
        lambda p, bt: model.prefill(p, bt)[1], params, batch
    )
    decode = jax.jit(model.decode).lower(params, caches_shape, step).compile()
    compile_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    logits, caches = prefill(params, batch)
    jax.block_until_ready(logits)
    prefill_s = time.perf_counter() - t0
    print(f"prefill {s} tokens x {b}: {prefill_s:.3f}s (smoke timing)")

    # NOTE on cache semantics: serve decodes against the *fixed* prefill
    # cache; decode steps after the first do not see the tokens decoded
    # before them (a cache that grows is still to come).
    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    out_tokens = [tok]
    first = None
    t0 = time.perf_counter()
    for i in range(args.gen):
        step = {"tokens": tok, "pos": jnp.full((b,), s + i, jnp.int32)}
        step_logits, _ = decode(params, caches, step)
        if first is None:
            first = step_logits
        key, k2 = jax.random.split(key)
        if args.temperature > 0:
            tok = jax.random.categorical(
                k2, step_logits[:, -1] / args.temperature, -1
            )[:, None].astype(jnp.int32)
        else:
            tok = jnp.argmax(step_logits[:, -1], -1)[:, None].astype(jnp.int32)
        out_tokens.append(tok)
    jax.block_until_ready(tok)
    decode_s = time.perf_counter() - t0
    print(
        f"decoded {args.gen} tokens x {b}: {decode_s:.3f}s (smoke timing); "
        f"compile {compile_s:.1f}s"
    )
    gen = np.concatenate([np.asarray(t) for t in out_tokens], 1)
    print("sample token ids:", gen[0][:16].tolist())
    return ServeRun(
        model=model, params=params, tokens=batch["tokens"], generated=gen,
        prefill_logits=logits, first_decode_logits=first, attn=attn,
        prefill_hlo=prefill_hlo, compile_s=compile_s, prefill_s=prefill_s,
        decode_s=decode_s,
    )


if __name__ == "__main__":
    main()
