"""The one traffic generator: it reads a mix's data file and draws from the seed.

A mix is a JSON file under ``bench/traffic/``. Its keys:

- ``driver``: the driver family that serves it (a module of ``bench/drivers``);
- prefill mixes: ``batch`` (prompts per request), ``prompt_lens`` (the set
  of prompt lengths; request ``r`` takes entry ``r mod len`` of the set
  shuffled by the seed, so every seed sends the same sizes in another
  order), ``clients`` (closed-loop clients, each waiting for its first
  token before it sends again) and ``check_requests`` (finished requests
  the correctness check samples, one of the longest among them);
- engine mixes: ``body`` (the engine body launched back to back), ``init``
  (``bits``: uniform 0/1; ``ints``: uniform in ``[0, init_high)``),
  ``check_samples`` (launches whose input and output the check keeps, the
  first and the last among them) and ``compare_kind`` (the schedule kind
  a traced run also launches, for the per-layer comparison).

Token ids and states are drawn from ``--seed`` alone: the same seed gives
the same inputs.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

__all__ = ["load_mix", "rng", "PromptStream", "sample_indices"]


def load_mix(path: Path) -> dict:
    """The mix's parameters, checked for the keys its driver needs."""
    mix = json.loads(Path(path).read_text())
    need = {"prefill": ("batch", "prompt_lens", "clients", "check_requests"),
            "engine": ("body", "init", "check_samples", "compare_kind")}
    missing = [k for k in need.get(mix.get("driver"), ("driver",))
               if k not in mix]
    if missing:
        raise ValueError(f"traffic mix {path} lacks {missing}")
    return mix


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one stream of the run, from the seed alone."""
    return np.random.default_rng([int(seed), *[int(s) for s in stream]])


class PromptStream:
    """Request ``r``'s prompt token ids, ``(batch, length)`` int32.

    Args:
        mix: A prefill mix.
        vocab: Ids are uniform over ``[0, vocab)``.
        seed: The run's seed.
    """

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.batch = int(mix["batch"])
        lens = np.asarray(mix["prompt_lens"], np.int64)
        self.lens = rng(seed, 0).permutation(lens)
        self.vocab = int(vocab)
        self.seed = int(seed)

    @property
    def shapes(self) -> list:
        """Every ``(batch, length)`` the stream sends: the shapes to warm up."""
        return sorted({(self.batch, int(s)) for s in self.lens})

    def length(self, r: int) -> int:
        """Prompt length of request ``r``."""
        return int(self.lens[r % len(self.lens)])

    def prompt(self, r: int) -> np.ndarray:
        """Token ids of request ``r``."""
        return rng(self.seed, 1, r).integers(
            0, self.vocab, size=(self.batch, self.length(r)), dtype=np.int32)


def sample_indices(seed: int, n: int, k: int, must: tuple = ()) -> list:
    """``k`` distinct indices of ``range(n)`` drawn from the seed, ``must`` among them."""
    chosen = [i for i in dict.fromkeys(must) if 0 <= i < n]
    rest = [i for i in rng(seed, 2).permutation(n) if i not in chosen]
    return sorted(chosen + [int(i) for i in rest[: max(0, k - len(chosen))]])
