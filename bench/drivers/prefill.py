"""Driver family: a decoder model's prefill, served to closed-loop clients.

Each request is a batch of prompts from the mix. It is prefilled through
the program's ``Model.prefill``, and its first token is the argmax of the
last position's logits, brought to the host, as ``launch/serve.py`` does.
Those logits stay on the device for the check. A client sends its next
request once the first token of the last one has arrived. The weights come
from the configuration's reference module, drawn from the seed in one
jitted call and laid out as the program's parameters.
"""

from __future__ import annotations

import gc
import statistics
import time
from collections import namedtuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.core.harness import load_module, seed_key
from bench.core.traffic import PromptStream, rng, sample_indices

__all__ = ["Driver"]

Request = namedtuple("Request", "submit done length served logits")


def program_config(cfg: dict):
    """The program's ``ArchConfig`` at the sizes and dtype ``cfg`` states."""
    from repro.configs.base import get_config

    dtype = cfg["torch_dtype"]
    return get_config(cfg["program_arch"]).replace(
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], head_dim=cfg.get("head_dim", 0),
        rope_theta=float(cfg["rope_theta"]), norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        param_dtype=dtype, act_dtype=dtype, remat="none",
    )


class Driver:
    """Closed-loop prefill of one configuration under one mix.

    Args:
        cfg: The configuration file's contents.
        mix: The traffic mix.
        seed: The run's seed.
        config_dir: Where the configuration's reference module lives.
        spans: The harness's span factory.
        step_wrapper: Tests only: wraps the compiled step, to plant a fault.
        control: Check the control (the reference one precision step
            down) in the program's place, on the same requests.
    """

    def __init__(self, cfg, mix, seed, config_dir, spans, step_wrapper=None,
                 control=False):
        if int(mix["clients"]) != 1:
            raise ValueError("the prefill driver serves one closed-loop client")
        self.cfg, self.mix, self.seed, self.spans = cfg, mix, int(seed), spans
        self.ref = load_module(config_dir / cfg["reference"])
        self.stream = PromptStream(mix, cfg["vocab_size"], seed)
        self.step_wrapper = step_wrapper
        self.control = bool(control)
        self.records = []
        self.attempted = self.failed = 0
        self._notes = []

    # ----------------------------------------------------------- set-up
    def _params(self, key):
        dm = self.ref.dims(self.cfg)
        lw = jax.vmap(lambda i: self.ref.layer_weights(dm, key, i))(
            jnp.arange(dm[6]))
        gw = self.ref.global_weights(dm, key)
        block = {"norm1": {"w": lw["norm1"]},
                 "mixer": {k: lw[k] for k in ("wq", "wk", "wv", "wo")},
                 "norm2": {"w": lw["norm2"]},
                 "ffn": {k: lw[k] for k in ("w1", "w3", "w2")}}
        tree = {"embed": {"e": gw["embed"]}, "final_norm": {"w": gw["final_norm"]},
                "stack": {"l0": block}, "unembed": gw["unembed"]}
        dtype = jnp.dtype(self.cfg["torch_dtype"])
        return jax.tree.map(lambda a: a.astype(dtype), tree)

    def setup(self, trace: bool = False) -> None:
        """Weights on the device, the step compiled and run on every shape."""
        from repro.autotune import choose_attn_impl
        from repro.models.model import Model

        arch = program_config(self.cfg)
        model = Model(arch)
        want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        self.key = seed_key(self.seed)
        got = jax.eval_shape(self._params, self.key)
        if jax.tree.structure(want) != jax.tree.structure(got) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype)
                for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
            raise ValueError("the drawn weights do not match the program's parameters")
        self.params = jax.jit(self._params)(self.key)

        def prefill_step(params, tokens):
            logits, caches = model.prefill(params, {"tokens": tokens})
            last = logits[:, -1]
            return jnp.argmax(last, -1).astype(jnp.int32), last, caches

        step = jax.jit(prefill_step)
        self.step = self.step_wrapper(step) if self.step_wrapper else step
        for b, s in self.stream.shapes:
            dec = choose_attn_impl(s, arch.n_heads, arch.hd)
            self._notes.append(
                f"attention at s={s}: impl {dec.impl}, kind {dec.kind}, "
                f"block {dec.block_q} (source {dec.source})")
            tok, _, caches = self.step(self.params, jnp.zeros((b, s), jnp.int32))
            np.asarray(tok)
            del caches

    # ----------------------------------------------------------- window
    def run_window(self, seconds: float) -> None:
        """Requests back to back until ``seconds`` have passed."""
        spans = self.spans
        t0 = time.perf_counter()
        r = 0
        while True:
            submit = time.perf_counter()
            with spans("prepare"):
                tokens = jax.device_put(self.stream.prompt(r))
            with spans("dispatch"):
                tok, logits, caches = self.step(self.params, tokens)
            with spans("wait"):
                tok.block_until_ready()
            with spans("first_token"):
                served = np.asarray(tok)
            done = time.perf_counter()
            del caches
            self.attempted += 1
            self.records.append(Request(submit, done, self.stream.length(r), served, logits))
            r += 1
            if done - t0 >= seconds:
                break
        self.window_s = done - t0

    def trace_extra(self) -> None:
        """Nothing beyond the window is traced for prefill."""

    # ----------------------------------------------------------- results
    def end_to_end(self) -> dict:
        """``prefill_tok_s`` and ``ttft_p95_ms`` of the window."""
        tokens = sum(self.stream.batch * r.length for r in self.records)
        ttft = [(r.done - r.submit) * 1e3 for r in self.records]
        return {"prefill_tok_s": tokens / self.window_s,
                "ttft_p95_ms": float(np.percentile(ttft, 95))}

    def facts(self) -> dict:
        """What the per-layer readers count from."""
        return {"cfg": self.cfg, "batch": self.stream.batch,
                "prompt_lens": [r.length for r in self.records],
                "window_s": self.window_s, "itemsize": 2}

    def notes(self) -> list:
        """Earlier lines: decisions, request count and the median TTFT."""
        ttft = [(r.done - r.submit) * 1e3 for r in self.records]
        between = [(b.submit - a.done) * 1e3
                   for a, b in zip(self.records, self.records[1:])]
        return self._notes + [
            f"{len(self.records)} requests in {self.window_s:.3f} s; "
            f"ttft median {statistics.median(ttft):.3f} ms, slowest "
            f"{sorted(ttft)[-3:]} ms; longest host gaps between requests "
            f"{sorted(between)[-3:]} ms"]

    def release(self) -> None:
        """Drop the program's weights and compiled step."""
        del self.params, self.step
        gc.collect()

    def check(self) -> list:
        """The served logits of sampled requests against the reference's.

        Requests are drawn from the seed, one of the longest among them. Two
        numbers: ``last_logits_rel_err``, the largest over the sampled
        prompts of ``|got - ref| / |ref|`` (L2 norms over the vocabulary)
        between the last position's logits the timed step returned and the
        float32 reference's; and ``served_not_argmax``, the prompts whose
        served token is not the argmax of those logits. In control mode
        the control's logits, and their argmax as the served token, take
        the program's place.
        """
        n = len(self.records)
        top = max(r.length for r in self.records)
        longest = rng(self.seed, 4).choice(
            [i for i in range(n) if self.records[i].length == top])
        idx = sample_indices(self.seed, n, int(self.mix["check_requests"]),
                             must=(int(longest),))
        by_len = {}
        for i in idx:
            by_len.setdefault(self.records[i].length, []).append(i)
        rel_err, not_argmax = 0.0, 0
        for group in by_len.values():
            prompts = np.concatenate([self.stream.prompt(i) for i in group])
            want = self.ref.last_logits(self.cfg, self.key, prompts)
            if self.control:
                got = self.ref.last_logits(self.cfg, self.key, prompts, "int8")
                served = np.argmax(got, -1)
            else:
                got = np.concatenate([np.asarray(self.records[i].logits, np.float32)
                                      for i in group])
                served = np.concatenate([self.records[i].served for i in group])
            err = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
            rel_err = max(rel_err, float(np.max(np.where(np.isfinite(err), err, np.inf))))
            not_argmax += int(np.sum(served != np.argmax(got, -1)))
        limits = self.cfg["limits"]
        return [{"name": "last_logits_rel_err", "value": rel_err,
                 "limit": limits["last_logits_rel_err"]},
                {"name": "served_not_argmax", "value": not_argmax,
                 "limit": limits["served_not_argmax"]}]
