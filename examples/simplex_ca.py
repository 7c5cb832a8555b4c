"""Cellular automaton on simplex domains — the paper's flagship
application (§5.1: CA2D with periodic bounds, CA3D free bounds).

Runs Conway's game of life on a triangular domain with the H-grid
kernel and renders generations as ASCII; then steps a 3D tetrahedral
CA with the exact table schedule and prints live-cell counts.

Run:  PYTHONPATH=src python examples/simplex_ca.py [--steps 8] [--n 64]

Multi-device mode (DESIGN.md §7) runs a long sharded m=3 CA over k
devices with fold-partition load balancing, checkpointing every few
generations and surviving a simulated worker loss via the watchdog:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  PYTHONPATH=src python examples/simplex_ca.py --devices 8 \\
      [--steps 12] [--fail-at 5] [--executor engine|spmd]

The final sharded state is asserted bit-equal to an uninterrupted
single-device engine run.
"""

import argparse
import os
import shutil
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.kernels import ops
from repro.kernels import ref as R


def render(state, max_rows=24):
    s = np.asarray(state)
    n = s.shape[0]
    step = max(1, n // max_rows)
    lines = []
    for r in range(0, n, step):
        row = s[r, : r + 1 : step]
        lines.append(" ".join("o" if c else "." for c in row))
    return "\n".join(lines)


def single_device_demo(args):
    n = args.n
    key = jax.random.PRNGKey(42)
    state = (jax.random.uniform(key, (n, n)) < 0.35).astype(jnp.int32)
    state = state * R.tril_mask(n, jnp.int32)
    print(f"2-simplex CA, n={n}, H-grid kernel "
          f"({(n//args.rho)//2}x{(n//args.rho)+1} blocks vs "
          f"{(n//args.rho)**2} for BB)")
    for t in range(args.steps):
        alive = int(state.sum())
        print(f"\n-- generation {t} (alive={alive}) --")
        print(render(state))
        state = ops.simplex_ca2d(state, rho=args.rho, kind="hmap")

    print("\n3-simplex CA (free boundaries, exact table schedule):")
    n3 = 32
    s3 = (jax.random.uniform(key, (n3, n3, n3)) < 0.3).astype(jnp.int32)
    s3 = s3 * R.tetra_mask(n3, jnp.int32)
    for t in range(4):
        print(f"  gen {t}: alive={int(s3.sum())}")
        s3 = ops.simplex_ca3d(s3, rho=4, kind="table")
    print(f"  gen 4: alive={int(s3.sum())}")


def sharded_demo(args):
    """Long sharded m=3 CA: fold partition + checkpoints + watchdog."""
    from repro.checkpoint import checkpointing as ckpt
    from repro.distributed.fault_tolerance import watchdog_restart
    from repro.distributed.simplex_sharding import (
        ShardedSimplexCA, shard_mesh, shard_skew,
    )

    k = args.devices
    if jax.device_count() < k:
        raise SystemExit(
            f"need {k} devices, found {jax.device_count()}; emulate with\n"
            "  XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{k} PYTHONPATH=src python examples/simplex_ca.py "
            f"--devices {k}"
        )
    n = args.n3
    mesh = shard_mesh(k)
    runner = ShardedSimplexCA(3, n, k, kind="table", mesh=mesh)
    print(f"3-simplex CA sharded over {k} devices "
          f"(n={n}, {runner.base.steps} blocks, fold skew "
          f"{shard_skew(runner.base, k):.4f})")
    for sh in runner.shards:
        print(f"  shard {sh.shard.index}: {sh.steps} blocks, "
              f"step ranges {sh.ranges}")

    key = jax.random.PRNGKey(7)
    init = (jax.random.uniform(key, (n, n, n)) < 0.3).astype(jnp.int32)
    init = np.asarray(init * R.tetra_mask(n, jnp.int32))

    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="simplex_ca_ckpt_")
    fail_at = {"step": args.fail_at}  # one-shot simulated worker loss

    def train(start_step):
        """Resume-from-checkpoint CA loop (the watchdog's train_fn)."""
        if start_step is None:
            state, t0 = init, 0
        else:
            tree, t0 = ckpt.restore_latest(ckpt_dir, {"state": init})
            state = np.asarray(tree["state"])
            print(f"  [watchdog] resumed from checkpoint step {t0}")
        state = jnp.asarray(state)
        for t in range(t0, args.steps):
            if fail_at["step"] is not None and t == fail_at["step"]:
                fail_at["step"] = None
                raise RuntimeError(
                    f"simulated worker loss at generation {t}"
                )
            state = runner.step(state, executor=args.executor)
            if (t + 1) % args.ckpt_every == 0 or t + 1 == args.steps:
                ckpt.save(ckpt_dir, t + 1, {"state": np.asarray(state)})
            print(f"  gen {t + 1}: alive={int(jnp.sum(state))}")
        return state

    restarts = watchdog_restart(train, ckpt_dir)
    print(f"watchdog restarts: {restarts}")
    tree, step = ckpt.restore_latest(ckpt_dir, {"state": init})
    final = np.asarray(tree["state"])

    # ground truth: uninterrupted single-device engine run
    want = init
    for _ in range(args.steps):
        want = np.asarray(ops.simplex_ca_md(jnp.asarray(want), kind="table"))
    exact = np.array_equal(want, final)
    print(f"sharded result bit-equals single-device engine: {exact}")
    if args.ckpt_dir is None:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    if not exact:
        raise SystemExit("sharded CA diverged from single-device engine")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=64)
    ap.add_argument("--n3", type=int, default=32,
                    help="m=3 side length for --devices mode")
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--rho", type=int, default=8)
    ap.add_argument("--devices", type=int, default=0,
                    help="shard the m=3 CA over k devices (0 = off)")
    ap.add_argument("--executor", choices=("engine", "spmd"),
                    default="engine")
    ap.add_argument("--fail-at", type=int, default=None,
                    help="simulate a worker loss at this generation")
    ap.add_argument("--ckpt-every", type=int, default=3)
    ap.add_argument("--ckpt-dir", type=str, default=None)
    args = ap.parse_args()
    enable_compile_cache()

    if args.devices:
        sharded_demo(args)
    else:
        single_device_demo(args)


if __name__ == "__main__":
    main()
