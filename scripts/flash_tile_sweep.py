"""Time the folded flash kernel at each square tile, per grid step, on a TPU.

For every sequence length and tile side asked for, one launch of
``flash_attention(kind='folded')`` at yi-6b's attention widths (batch 1,
32 query and 4 kv heads of 128, bf16) is compiled, warmed up and timed
on the host clock around ``block_until_ready``. The line for each cell
gives the median time of a launch over ``--repeats`` timed rounds, the
grid steps it walks (heads x folded steps), the cost of one step, and
the largest difference from the 128 tile's output at the same length.
The lines are printed and written as JSON to ``--out``.

Run on the chip, e.g.:

    PYTHONPATH=src python scripts/flash_tile_sweep.py --seqs 1024,8192 \\
        --tiles 128,256,512,1024 --out chiprun_out/flash_tile_sweep.jsonl

A host with no TPU refuses to run: the numbers would time the Pallas
interpreter, not the kernel.
"""

from __future__ import annotations

import argparse
import functools
import json
import statistics
import sys
import time
from pathlib import Path

B, HQ, HKV, D = 1, 32, 4, 128


def _ints(text: str) -> list:
    return [int(x) for x in text.split(",") if x]


def _time_launch(fn, args, seconds: float, repeats: int) -> float:
    """Median seconds of one launch over ``repeats`` timed rounds."""
    out = fn(*args)
    out.block_until_ready()
    t0 = time.perf_counter()
    fn(*args).block_until_ready()
    one = max(time.perf_counter() - t0, 1e-6)
    calls = max(1, int(seconds / repeats / one))
    rounds = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        out.block_until_ready()
        rounds.append((time.perf_counter() - t0) / calls)
    return statistics.median(rounds)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seqs", type=_ints, default=[1024, 8192])
    ap.add_argument("--tiles", type=_ints, default=[128, 256, 512, 1024])
    ap.add_argument("--seconds", type=float, default=2.0,
                    help="timed seconds per cell, over all rounds")
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--out", type=Path, default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.flash_attention import flash_attention, flash_grid_steps

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 1
    lines = []
    for s in args.seqs:
        ks = jax.random.split(jax.random.PRNGKey(s), 3)
        q = jax.random.normal(ks[0], (B, HQ, s, D), jnp.bfloat16)
        k = jax.random.normal(ks[1], (B, HKV, s, D), jnp.bfloat16)
        v = jax.random.normal(ks[2], (B, HKV, s, D), jnp.bfloat16)
        base = None
        for tile in args.tiles:
            if s % tile:
                continue
            fn = jax.jit(functools.partial(
                flash_attention, kind="folded", block_q=tile, block_kv=tile,
                interpret=False))
            t0 = time.perf_counter()
            out = np.asarray(fn(q, k, v).astype(jnp.float32))
            compile_s = time.perf_counter() - t0
            if base is None:
                base = out
            launch_s = _time_launch(fn, (q, k, v), args.seconds, args.repeats)
            nq = s // tile
            steps = B * HQ * flash_grid_steps(nq, "folded" if nq > 1 else "bb")
            line = {
                "device": dev.device_kind, "seq": s, "tile": tile, "nq": nq,
                "launch_ms": launch_s * 1e3, "steps": steps,
                "us_per_step": launch_s * 1e6 / steps,
                "max_abs_diff_vs_first_tile": float(np.abs(out - base).max()),
                "compile_s": compile_s,
            }
            lines.append(line)
            print(json.dumps(line), flush=True)
    if args.out is not None:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text("".join(json.dumps(x) + "\n" for x in lines))
    return 0


if __name__ == "__main__":
    sys.exit(main())
