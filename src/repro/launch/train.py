"""End-to-end trainer with checkpoint/restart fault tolerance.

Runs for real on CPU-sized configs (the examples use it); the same code
path drives the production mesh on TPU.  Features exercised here:
deterministic data (step -> batch), atomic checkpoints + resume-latest,
grad accumulation, and the folded-simplex attention schedule.

  PYTHONPATH=src python -m repro.launch.train --arch yi-6b --smoke \
      --steps 200 --ckpt-dir /tmp/ckpt --resume
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--schedule-steps", type=int, default=0,
                    help="LR schedule horizon (defaults to --steps); set "
                    "explicitly when a run will be interrupted + resumed")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--d-model", type=int, default=0,
                    help="override width (e.g. ~100M params presets)")
    ap.add_argument("--n-layers", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.checkpoint import checkpointing as ckpt
    from repro.compile_cache import enable_compile_cache
    from repro.configs.ALL import REDUCED
    from repro.configs.base import get_config
    from repro.data.pipeline import SyntheticLM
    from repro.models.model import Model
    from repro.optim.optimizer import make_optimizer, warmup_cosine

    enable_compile_cache()

    cfg = REDUCED[args.arch]() if args.smoke else get_config(args.arch)
    over = {"act_dtype": "float32", "param_dtype": "float32", "remat": "none"}
    if args.d_model:
        over["d_model"] = args.d_model
    if args.n_layers:
        over["n_layers"] = args.n_layers
    cfg = cfg.replace(**over)

    model = Model(cfg)
    horizon = args.schedule_steps or args.steps
    opt = make_optimizer(
        cfg.optimizer, warmup_cosine(args.lr, horizon // 10 + 1, horizon)
    )
    data = SyntheticLM(cfg.vocab, args.seq, args.batch, seed=args.seed)

    params = model.init(jax.random.PRNGKey(args.seed))
    opt_state = opt.init(params)
    step0 = 0
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
    print(f"arch={cfg.name} params={n_params:,} steps={args.steps}")

    if args.resume and args.ckpt_dir:
        proto = {"params": params, "opt": opt_state}
        restored, s = ckpt.restore_latest(args.ckpt_dir, proto)
        if restored is not None:
            params, opt_state = restored["params"], restored["opt"]
            step0 = s
            print(f"resumed from step {s}")

    nmb = args.microbatches

    @jax.jit
    def train_step(params, opt_state, step, batch):
        def loss_fn(p, mb):
            l, m = model.loss(p, mb)
            return l

        if nmb > 1:
            mbs = jax.tree_util.tree_map(
                lambda x: x.reshape((nmb, x.shape[0] // nmb) + x.shape[1:]),
                batch,
            )
            zero = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )

            def acc(c, mb):
                g_acc, l_acc = c
                l, g = jax.value_and_grad(loss_fn)(params, mb)
                return (
                    jax.tree_util.tree_map(lambda a, b: a + b, g_acc, g),
                    l_acc + l,
                ), None

            (grads, loss), _ = jax.lax.scan(acc, (zero, 0.0), mbs)
            grads = jax.tree_util.tree_map(lambda g: g / nmb, grads)
            loss = loss / nmb
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        new_p, new_o = opt.update(grads, opt_state, params, step)
        return new_p, new_o, loss

    t0 = time.time()
    losses = []
    for step in range(step0, args.steps):
        batch = data.batch_at(step)
        params, opt_state, loss = train_step(
            params, opt_state, jnp.asarray(step), batch
        )
        losses.append(float(loss))
        if step % args.log_every == 0 or step == args.steps - 1:
            tok_s = args.batch * args.seq * (step - step0 + 1) / (time.time() - t0)
            print(f"step {step:5d}  loss {float(loss):.4f}  tok/s {tok_s:,.0f}")
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            ckpt.save(args.ckpt_dir, step + 1, {"params": params, "opt": opt_state})
            print(f"checkpoint @ {step + 1}")
    print(f"first-loss {losses[0]:.4f}  last-loss {losses[-1]:.4f}")
    return losses


if __name__ == "__main__":
    main()
