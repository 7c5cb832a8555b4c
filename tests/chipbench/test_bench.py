"""Tests of the chip benchmark that a CPU can run.

They cover the yardstick (counts, peaks, the trace reduction on a trace
recorded on a TPU v5e), the refusal to measure without a chip, each driver
end to end at a tiny size with Pallas interpreted, the yi-6b reference
against the program, the controls and the faults ``correct`` must catch.
Nothing here touches libtpu while it is imported, and the JAX settings a
run makes are undone when the module ends.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q tests/chipbench
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench.core import counts, device, harness, trace  # noqa: E402

TESTDATA = ROOT / "bench" / "testdata"
YI = json.loads((ROOT / "bench" / "configs" / "yi-6b.json").read_text())
TINY_YI = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 1,
           "intermediate_size": 128, "vocab_size": 512, "num_hidden_layers": 2}
TINY_LIMIT = 1e-3


@pytest.fixture(scope="module", autouse=True)
def _restore_jax_settings():
    """A run points JAX's persistent cache at the checkout; undo it."""
    import jax
    from jax._src import compilation_cache

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    names = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    yield
    if env is None:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = env
    for n, v in before.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


# ------------------------------------------------------------------ counts
def test_yi_matmul_flops_per_token():
    assert 2 * counts.lm_matmul_params(YI) == 11_072_962_560


def test_simplex_blocks_and_bytes_at_16384():
    assert counts.simplex_blocks(2, 16384 // 128) == 8256
    assert counts.simplex_elements(2, 16384) == 134_225_920
    assert counts.engine_needed_bytes(2, 16384, 4) == pytest.approx(1.0738e9, rel=1e-4)
    assert counts.simplex_blocks(3, 4) == 20


@pytest.mark.parametrize("seq", [1, 7, 64, 128])
def test_attention_count_is_the_causal_work(seq):
    """The count is the causal half whatever walks it: fold, box or chunks."""
    from repro.kernels.flash_attention import flash_grid_steps

    mask = np.tril(np.ones((seq, seq)))
    assert counts.causal_attention_flops(2, 3, 16, seq) == 4 * 2 * 3 * 16 * mask.sum()
    if seq % 16 == 0 and seq // 16 >= 2:
        nq = seq // 16
        assert flash_grid_steps(nq, "folded") != flash_grid_steps(nq, "bb")


def test_prefill_flops_yi_8k():
    f = counts.prefill_flops(YI, 8192)
    assert f == pytest.approx(11_072_962_560 * 8192 + 32 * 4 * 4096 * 8192 * 8193 / 2
                              + 2 * 4096 * 64000)
    assert f == pytest.approx(1.083e14, rel=1e-3)


# ------------------------------------------------------------------- peaks
def test_peaks_lookup():
    assert device.peaks_for("TPU v5 lite").bf16_flops == 197e12
    with pytest.raises(KeyError):
        device.peaks_for("TPU v9 imaginary")


# ------------------------------------------------------------------- trace
@pytest.fixture(scope="module")
def engine_trace():
    """CA at n = 2048 on a v5e: eight launches in the window, two of ``bb``."""
    s = trace.reduce_trace(TESTDATA / "engine_ca_window.xplane.pb")
    s.compare = trace.reduce_trace(TESTDATA / "engine_ca_compare.xplane.pb")
    return s


def test_trace_reduction_kernel_and_idle(engine_trace):
    s = engine_trace
    assert s.window_s > 0
    assert 0 < s.busy_s <= s.window_s
    mosaic = [o for o in s.ops() if o.mosaic]
    assert len(mosaic) == 8
    assert s.kernel_ns() == sum(o.end - o.start for o in mosaic)
    assert len([o for o in s.compare.ops() if o.mosaic]) == 2
    per_launch = s.kernel_ns() / 8, s.compare.kernel_ns() / 2
    assert per_launch[1] > per_launch[0]  # the box walks more tiles
    ops = s.ops(chip=0)
    span = max(o.end for o in ops) - min(o.start for o in ops)
    gaps = s.idle_gaps()
    assert sum(e - g for g, e in gaps) / 1e9 == pytest.approx(span / 1e9 - s.busy_s,
                                                              rel=1e-6)
    bd = s.breakdown()
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert bd["device_ops"][0][0].endswith("(pallas)")
    assert all(name.startswith("bench.") or name == "outside bench spans"
               for name, _ in bd["idle_gaps"])


def test_union_and_self_times():
    assert trace.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    ops = [trace.Op("while", 0, 100, False), trace.Op("a", 10, 30, False),
           trace.Op("b", 40, 90, True)]
    assert trace.self_times(ops) == {"while": 30, "a": 20, "b": 50}


# ------------------------------------------------------------- no chip, no run
def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", "m2.ca",
         "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300, cwd=ROOT)
    assert out.returncode != 0
    assert "{" not in out.stdout
    assert "needs a TPU" in out.stderr


def test_run_fails_without_the_program(tmp_path):
    """A checkout of the benchmark alone, with no program, gives no result."""
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "m2.accum", "--seed", "5",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=tmp_path,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=""))
    assert out.returncode != 0
    assert "{" not in out.stdout


# ---------------------------------------------------------- drivers end to end
def _run(capsys, workload, overrides, trace_=False, seconds=0.5):
    rc = harness.run_cell(workload, 2**33 + 3, seconds, trace_, time.time(),
                          overrides=overrides, require_chip=False)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _yi(extra=None, **driver):
    return {"config": {**TINY_YI, "torch_dtype": "float32",
                       "limits": {"last_logits_rel_err": TINY_LIMIT,
                                  "served_not_argmax": 0}},
            "mix": {"prompt_lens": [32], "check_requests": 2, **(extra or {})},
            "driver": driver}


def _m2(**driver):
    return {"config": {"n": 32}, "driver": driver}


@pytest.mark.parametrize("workload", ["yi6b.prefill_1k", "yi6b.prefill_8k"])
def test_prefill_driver_end_to_end(capsys, workload):
    line = _run(capsys, workload, _yi())
    assert line["correct"] is True
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) <= {"prefill_tok_s", "ttft_p95_ms", "setup_s"}
    assert "prefill_tok_s" in line["metrics"] and "setup_s" in line["metrics"]
    assert list(line)[-1] == "checks"
    assert line["checks"]["served_not_argmax"] == {"value": 0, "limit": 0}
    assert 0 <= line["checks"]["last_logits_rel_err"]["value"] < TINY_LIMIT


@pytest.mark.parametrize("workload", ["m2.ca", "m2.accum"])
@pytest.mark.parametrize("traced", [False, True])
def test_engine_driver_end_to_end(capsys, workload, traced):
    line = _run(capsys, workload, _m2(), trace_=traced)
    assert line["correct"] is True
    assert line["checks"]["mismatched_elements"] == {"value": 0, "limit": 0}
    if traced:
        assert "breakdown" in line and "window_s" in line["device"]
    else:
        assert "elements_per_s" in line["metrics"]


# ----------------------------------------------------------- planted faults
def _altered_token(step):
    def bad(params, tokens):
        tok, logits, caches = step(params, tokens)
        return (tok + 1) % TINY_YI["vocab_size"], logits, caches
    return bad


def _altered_logits(step):
    def bad(params, tokens):
        tok, logits, caches = step(params, tokens)
        return tok, logits.at[:, 0].add(1e3), caches
    return bad


def _unchanged_state(step):
    return lambda state: state


def _altered_answer(step):
    def bad(state):
        out = step(state)
        return out.at[-1, 0].set(out[-1, 0] + 7)
    return bad


@pytest.mark.parametrize("workload,overrides", [
    ("yi6b.prefill_1k", _yi(step_wrapper=_altered_token)),
    ("yi6b.prefill_8k", _yi(step_wrapper=_altered_logits)),
    ("m2.ca", _m2(step_wrapper=_unchanged_state)),
    ("m2.ca", _m2(step_wrapper=_altered_answer)),
    ("m2.accum", _m2(step_wrapper=_unchanged_state)),
    ("m2.accum", _m2(step_wrapper=_altered_answer)),
], ids=["token-altered", "logits-altered", "ca-unchanged", "ca-altered", "accum-unchanged",
        "accum-altered"])
def test_planted_fault_reads_incorrect(capsys, workload, overrides):
    line = _run(capsys, workload, overrides)
    assert line["correct"] is False


# ------------------------------------------------------ the yi-6b reference
@pytest.fixture(scope="module")
def yi_ref():
    return harness.load_module(ROOT / "bench" / "configs" / "yi-6b_reference.py")


def test_layer_weights_same_under_vmap(yi_ref):
    import jax

    cfg = {**YI, **TINY_YI}
    dm = yi_ref.dims(cfg)
    key = harness.seed_key(2**33 + 9)
    stacked = jax.vmap(lambda i: yi_ref.layer_weights(dm, key, i))(np.arange(2))
    one = yi_ref.layer_weights(dm, key, 1)
    for name in one:
        np.testing.assert_array_equal(np.asarray(stacked[name][1], np.float32),
                                      np.asarray(one[name], np.float32))


def test_reference_matches_program_prefill(yi_ref):
    """At a reduced size in float32, the program's logits are the reference's."""
    import jax
    import jax.numpy as jnp

    from repro.models.model import Model

    drv = harness.load_module(ROOT / "bench" / "drivers" / "prefill.py")
    cfg = {**YI, **TINY_YI, "torch_dtype": "float32"}
    d = drv.Driver(cfg, {"clients": 1, "batch": 1, "prompt_lens": [32],
                         "check_requests": 1}, 11, ROOT / "bench" / "configs",
                   harness.Spans())
    key = harness.seed_key(11)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), d._params(key))
    tokens = d.stream.prompt(0)
    model = Model(drv.program_config(cfg))
    logits, _ = jax.jit(model.prefill)(params, {"tokens": jnp.asarray(tokens)})
    ref = yi_ref.last_logits(cfg, key, tokens)
    np.testing.assert_allclose(np.asarray(logits[0, -1]), ref[0],
                               rtol=2e-4, atol=2e-4)


def test_reference_blocks_of_prompts_agree(yi_ref, monkeypatch):
    """Prompts taken in blocks give what each gives alone."""
    cfg = {**YI, **TINY_YI}
    key = harness.seed_key(2**33 + 13)
    tokens = np.random.default_rng(1).integers(0, 512, size=(3, 64), dtype=np.int32)
    together = yi_ref.last_logits(cfg, key, tokens)
    monkeypatch.setattr(yi_ref, "ROWS", 64)
    alone = yi_ref.last_logits(cfg, key, tokens)
    assert together.shape == (3, 512)
    np.testing.assert_allclose(together, alone, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("workload,overrides", [
    ("yi6b.prefill_1k", _yi(control=True)),
    ("m2.ca", _m2(control=True)),
    ("m2.accum", _m2(control=True)),
], ids=["yi-int8", "ca-off-domain-zeroed", "accum-int16"])
def test_control_reads_incorrect(capsys, workload, overrides):
    """The control, checked in the program's place, fails the cell's check."""
    line = _run(capsys, workload, overrides)
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


# ------------------------------------------------------------ metric readers
def _reader(name):
    return harness.load_module(ROOT / "bench" / "metrics" / f"{name}.py")


def _summary(kernel_ns_window, kernel_ns_compare=0.0):
    ops = [trace.Op("fusion.1", 0, 100, False),
           trace.Op("closed_call.1 (pallas)", 100, 100 + kernel_ns_window, True)]
    s = trace.TraceSummary(window=(0, 1e9), device_ops=[ops],
                           spans=[("bench.window", 0, 1e9)])
    if kernel_ns_compare:
        s.compare = trace.TraceSummary(window=(2e9, 3e9), device_ops=[[trace.Op(
            "closed_call.2 (pallas)", 2e9, 2e9 + kernel_ns_compare, True)]])
    return s


V5E = device.peaks_for("TPU v5 lite")


def test_flash_attn_roofline_reader():
    facts = {"cfg": YI, "batch": 1, "prompt_lens": [8192], "itemsize": 2,
             "window_s": 1.0}
    flops = 32 * counts.causal_attention_flops(1, 32, 128, 8192)
    kernel_ns = 1e9 * flops / V5E.bf16_flops * 10  # ten times the roofline
    got = _reader("flash_attn_roofline").read(_summary(kernel_ns), facts, V5E)
    assert got == pytest.approx(10.0)
    assert _reader("flash_attn_roofline").read(_summary(0), facts, V5E) is None


def test_engine_readers():
    facts = {"m": 2, "n": 16384, "itemsize": 4, "launches": 2, "window_s": 1.0,
             "compare_launches": 1}
    need_s = 2 * counts.engine_needed_bytes(2, 16384, 4) / V5E.hbm_bytes_s
    s = _summary(4 * need_s * 1e9, kernel_ns_compare=3 * need_s * 1e9)
    assert _reader("engine_roofline").read(s, facts, V5E) == pytest.approx(25.0)
    assert _reader("engine_speedup_vs_bb").read(s, facts, V5E) == pytest.approx(1.5)
    idle = _reader("device_idle.engine").read(s, facts, V5E)
    assert 0 < idle < 100


def test_prefill_mfu_reader():
    facts = {"cfg": YI, "batch": 1, "prompt_lens": [1024] * 10, "window_s": 1.0}
    want = 100 * 10 * counts.prefill_flops(YI, 1024) / V5E.bf16_flops
    assert _reader("prefill_mfu").read(None, facts, V5E) == pytest.approx(want)
