"""Substrate: optimizer convergence, data determinism, checkpoint
fault tolerance, mixer train/decode consistency, roofline cost model."""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpointing as C
from repro.data.pipeline import SyntheticLM, host_shard
from repro.optim.optimizer import clip_by_global_norm, make_optimizer, warmup_cosine

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("kind", ["adamw", "adafactor"])
def test_optimizer_converges(kind):
    params = {"a": jax.random.normal(KEY, (16, 8)), "b": {"c": jnp.ones((8,))}}
    opt = make_optimizer(kind, warmup_cosine(1e-2, 10, 200))

    def lossf(p):
        return jnp.sum((p["a"] @ p["b"]["c"] - 1.0) ** 2)

    st = opt.init(params)
    l0 = float(lossf(params))
    p = params
    for i in range(40):
        g = jax.grad(lossf)(p)
        p, st = opt.update(g, st, p, jnp.asarray(i))
    assert float(lossf(p)) < 0.3 * l0


def test_grad_clip():
    g = {"x": jnp.full((4,), 100.0)}
    clipped, gn = clip_by_global_norm(g, 1.0)
    assert abs(float(jnp.linalg.norm(clipped["x"])) - 1.0) < 1e-5
    assert float(gn) == pytest.approx(200.0)


def test_adafactor_state_is_factored():
    params = {"w": jnp.zeros((64, 32))}
    opt = make_optimizer("adafactor", warmup_cosine(1e-3, 1, 10))
    st = opt.init(params)
    assert st["f"]["w"]["vr"].shape == (64,)
    assert st["f"]["w"]["vc"].shape == (32,)


def test_data_deterministic_and_restartable():
    d1 = SyntheticLM(1000, 32, 8, seed=3)
    d2 = SyntheticLM(1000, 32, 8, seed=3)
    b5a = d1.batch_at(5)
    for s in [0, 1, 2]:
        d2.batch_at(s)  # different call history
    b5b = d2.batch_at(5)
    assert np.array_equal(np.asarray(b5a["tokens"]), np.asarray(b5b["tokens"]))
    b6 = d1.batch_at(6)
    assert not np.array_equal(np.asarray(b5a["tokens"]), np.asarray(b6["tokens"]))


def test_host_shard_partitions_batch():
    d = SyntheticLM(100, 16, 8)
    b = d.batch_at(0)
    parts = [host_shard(b, i, 4)["tokens"] for i in range(4)]
    assert all(p.shape[0] == 2 for p in parts)
    assert np.array_equal(
        np.concatenate([np.asarray(p) for p in parts]), np.asarray(b["tokens"])
    )


def test_checkpoint_roundtrip_and_atomicity():
    tree = {
        "params": {"w": jnp.arange(12.0).reshape(3, 4),
                   "stack": (jnp.ones((2, 2)), jnp.zeros(3))},
        "opt": {"m": {"w": jnp.full((3, 4), 0.5)}},
    }
    with tempfile.TemporaryDirectory() as d:
        C.save(d, 3, tree)
        C.save(d, 5, tree)
        assert C.list_steps(d) == [3, 5]
        got, step = C.restore_latest(d, tree)
        assert step == 5
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(tree)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # simulated crash mid-save: .tmp is never picked up
        os.makedirs(os.path.join(d, "step_00000009.tmp"))
        assert C.latest_step(d) == 5
        # corrupt LATEST pointer: falls back to newest complete
        open(os.path.join(d, "LATEST"), "w").write("garbage")
        assert C.latest_step(d) == 5


def test_train_restart_is_bit_exact():
    """Kill/restart mid-run reproduces the uninterrupted run exactly —
    the fault-tolerance contract (stateless data + atomic checkpoints)."""
    from repro.launch.train import main as train_main

    with tempfile.TemporaryDirectory() as d:
        args = ["--arch", "xlstm-350m", "--smoke", "--seq", "32",
                "--batch", "4", "--lr", "1e-3"]
        full = train_main(args + ["--steps", "6"])
        # interrupted run: 3 steps + checkpoint, then resume to 6 (the
        # LR schedule horizon must match the full run's)
        train_main(args + ["--steps", "3", "--schedule-steps", "6",
                           "--ckpt-dir", d, "--ckpt-every", "3"])
        resumed = train_main(args + ["--steps", "6", "--ckpt-dir", d,
                                     "--resume", "--ckpt-every", "100"])
        np.testing.assert_allclose(full[-1], resumed[-1], rtol=1e-5)


def test_hlo_cost_model_loop_awareness():
    from repro.roofline.hlo_cost import analyze_hlo

    single = 2 * 128**3
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)

    def f(x, w):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ w, None
            c, _ = jax.lax.scan(inner, c, None, length=4)
            return c, None
        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y

    r = analyze_hlo(jax.jit(f).lower(x, w).compile().as_text())
    assert r["flops"] == pytest.approx(12 * single)

    def g(w, x):  # grad+remat: fwd + recompute + 2x bwd per step
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(jax.checkpoint(body), x, None, length=6)
        return jnp.sum(y)

    r = analyze_hlo(jax.jit(jax.grad(g)).lower(w, x).compile().as_text())
    assert r["flops"] == pytest.approx(24 * single)


def test_roofline_terms_shape():
    from repro.roofline.analysis import roofline_terms

    rec = {
        "n_chips": 256, "flops": 1e18, "bytes_accessed": 1e15,
        "collectives": {"wire_bytes_per_chip": 1e11},
        "mode": "train", "params": int(1e9), "params_active": 1e9,
        "tokens": 1e6, "model_axis": 16, "microbatches": 1,
    }
    t = roofline_terms(rec)
    assert t["dominant"] in ("compute", "memory", "collective")
    assert t["compute_s"] == pytest.approx(1e18 / (256 * 197e12))
    assert 0 < t["useful_ratio"] <= 10


def test_device_peaks_table():
    from repro.roofline.analysis import PEAKS, backend_peaks, device_peaks

    v5e = device_peaks("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9)
    with pytest.raises(ValueError, match="no peaks recorded"):
        device_peaks("TPU v99")
    assert backend_peaks() in PEAKS.values()  # CPU: the modeled chip
