"""Tests of device time by program scope (``bench/core/scopes.py``) and its readers.

They cover the map from compiled HLO text to scopes, the charge of each
op's self time to its scope, the per-layer FLOP counts, the four readers
on synthetic summaries (their ``None`` cases too), the rebuilt step against
the drivers' own, and a CA window recorded on a TPU v5e with its step's
compiled HLO text.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q tests/chipbench
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench.core import counts, device, harness, layer_counts, scopes, steps, trace  # noqa: E402
from bench.core.scopes import UNSCOPED  # noqa: E402

TESTDATA = ROOT / "bench" / "testdata"
YI = json.loads((ROOT / "bench" / "configs" / "yi-6b.json").read_text())
TINY_YI = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 1,
           "intermediate_size": 128, "vocab_size": 512, "num_hidden_layers": 2,
           "torch_dtype": "float32"}
V5E = device.peaks_for("TPU v5 lite")

HLO = """\
HloModule jit_f, is_scheduled=true

%fused_computation (param_0: bf16[8,8]) -> bf16[8,8] {
  %param_0 = bf16[8,8]{1,0} parameter(0)
  ROOT %dot.1 = bf16[8,8]{1,0} dot(bf16[8,8]{1,0} %param_0, bf16[8,8]{1,0} %param_0), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(f)/while/body/closed_call/layer.ffn/dot_general" stack_frame_id=4}
}

ENTRY %main.2 (x.1: bf16[8,8]) -> (bf16[8,8], bf16[8,8]) {
  %x.1 = bf16[8,8]{1,0} parameter(0), metadata={op_name="x"}
  %constant.1 = bf16[] constant(0)
  %fusion.3 = bf16[8,8]{1,0:T(8,128)(2,1)S(1)} fusion(%x.1), kind=kOutput, calls=%fused_computation, metadata={op_name="jit(f)/while/body/closed_call/layer.ffn/dot_general" stack_frame_id=3}, backend_config={"flag_configs":[]}
  %copy.1 = bf16[8,8]{0,1:T(8,128)(2,1)} copy(%fusion.3), backend_config={"flag_configs":[]}
  %copy.2 = bf16[8,8]{0,1} copy(%copy.1)
  %copy.3 = bf16[8,8]{0,1} copy(%x.1)
  %pad = bf16[16,8]{1,0} pad(%copy.3, %constant.1), padding=0_8x0_0, metadata={op_name="jit(f)/engine.pad/layer.norm/concatenate" stack_frame_id=7}
  %slice.0 = bf16[8,8]{1,0} slice(%pad), slice={[0:8], [0:8]}, metadata={op_name="jit(f)/slice"}
  %tuple.5 = (bf16[8,8]{1,0}, bf16[16,8]{1,0}) tuple(%slice.0, %pad)
  ROOT %engine_ca_hmap.1 = bf16[8,8]{1,0} custom-call(%copy.2, %pad), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/engine.kernel/engine_ca_hmap/pallas_call" stack_frame_id=8}
}
"""


# ------------------------------------------------------------------- the map
@pytest.mark.parametrize("op_name, want", [
    ("jit(f)/while/body/closed_call/layer.ffn/dot_general", "layer.ffn"),
    ("jit(f)/model.embed/layer.norm/rsqrt", "layer.norm"),
    ("jit(f)/engine.kernel/engine_ca_hmap/pallas_call", "engine.kernel"),
    ("jit(f)/while/body/dynamic_slice", None),
    ("jit(engine_ca_auto)/slice", None),
])
def test_scope_of_takes_the_innermost(op_name, want):
    assert scopes.scope_of(op_name) == want


def test_op_scopes_fusions_copies_and_nesting():
    got = scopes.op_scopes(HLO)
    assert got["dot.1"] == "layer.ffn"  # inside a fused computation
    assert got["fusion.3"] == "layer.ffn"  # a fusion's own metadata
    assert got["copy.1"] == "layer.ffn"  # no op_name: the operand's scope
    assert got["copy.2"] == "layer.ffn"  # ... through a chain of copies
    assert got["copy.3"] == UNSCOPED  # its operand has no scope
    assert got["pad"] == "layer.norm"  # nested: the innermost
    assert got["slice.0"] == UNSCOPED  # an op_name without a scope stays so
    assert got["tuple.5"] == "layer.norm"  # the first operand with a scope
    assert got["engine_ca_hmap.1"] == "engine.kernel"
    assert got["x.1"] == UNSCOPED and got["constant.1"] == UNSCOPED


def test_scope_self_ns_charges_every_op():
    ops = [trace.Op("while.3", 0, 100, False), trace.Op("fusion.3", 10, 30, False),
           trace.Op("engine_ca_hmap.1 (pallas)", 40, 90, True),
           trace.Op("pad", 100, 110, False), trace.Op("fusion.99", 110, 115, False)]
    s = trace.TraceSummary(window=(0, 200), device_ops=[ops])
    got = scopes.scope_self_ns(s, scopes.op_scopes(HLO))
    assert got == {UNSCOPED: 30 + 5, "layer.ffn": 20, "engine.kernel": 50,
                   "layer.norm": 10}
    assert sum(got.values()) == sum(trace.self_times(ops).values())
    line = scopes.shares_line(got)
    assert line.index("engine.kernel 43.48%") < line.index(f"{UNSCOPED} 30.43%")


# ------------------------------------------------------------------ counts
def test_layer_flops_add_up_to_the_model_matmuls():
    per_token = layer_counts.ffn_flops(YI, 1) + layer_counts.attn_proj_flops(YI, 1)
    assert per_token == 2 * counts.lm_matmul_params(YI)
    assert layer_counts.ffn_flops(YI, 1024) == 6 * 4096 * 11008 * 1024 * 32
    assert layer_counts.attn_proj_flops(YI, 2) == 2 * (2 * 4096 * 4096
                                                       + 2 * 4096 * 512) * 2 * 32


# ----------------------------------------------------------------- readers
def _reader(name):
    return harness.load_module(ROOT / "bench" / "metrics" / f"{name}.py")


PREFILL_MAP = {"fusion.103": "layer.ffn", "fusion.102": "layer.attn_out",
               "convert_bitcast_fusion.4": "layer.attn_qkv",
               "flash_folded.6": "layer.attn_core", "add_rsqrt_fusion.5": "layer.norm",
               "fusion.101": "layer.rope", "copy.20": UNSCOPED}


def _prefill(ns: dict, lens=(1024, 1024)):
    """A window whose ops take ``ns[name]`` each, one after the other."""
    ops, t = [], 0.0
    for name, d in ns.items():
        pallas = name.startswith("flash")
        ops.append(trace.Op(name + (" (pallas)" if pallas else ""), t, t + d, pallas))
        t += d
    s = trace.TraceSummary(window=(0, t), device_ops=[ops])
    facts = {"cfg": YI, "batch": 1, "prompt_lens": list(lens), "window_s": t / 1e9,
             "itemsize": 2, "op_scopes": dict(PREFILL_MAP)}
    return s, facts


def test_prefill_scope_readers():
    tokens = 2048
    ffn_ns = layer_counts.ffn_flops(YI, tokens) / V5E.bf16_flops / 0.8 * 1e9
    proj_ns = layer_counts.attn_proj_flops(YI, tokens) / V5E.bf16_flops / 0.5 * 1e9
    s, facts = _prefill({"fusion.103": ffn_ns, "fusion.102": proj_ns / 4,
                         "convert_bitcast_fusion.4": 3 * proj_ns / 4,
                         "flash_folded.6": 5e6, "add_rsqrt_fusion.5": 1e6,
                         "fusion.101": 2e6, "copy.20": 3e6, "not_in_map.1": 4e6})
    assert _reader("ffn_mfu").read(s, facts, V5E) == pytest.approx(80.0)
    assert _reader("attn_proj_mfu").read(s, facts, V5E) == pytest.approx(50.0)
    glue_us = (1e6 + 2e6 + 3e6 + 4e6) / 1e3 / tokens
    assert _reader("prefill_glue_us_per_token").read(s, facts, V5E) == pytest.approx(glue_us)
    assert set(facts["scope_self_ns"]) == {"layer.ffn", "layer.attn_out", "layer.attn_qkv",
                                           "layer.attn_core", "layer.norm", "layer.rope",
                                           UNSCOPED}


def test_scope_shares_print_once(capsys):
    s, facts = _prefill({"fusion.103": 3e6, "copy.20": 1e6})
    for name in ("ffn_mfu", "attn_proj_mfu", "prefill_glue_us_per_token"):
        _reader(name).read(s, facts, V5E)
    lines = [ln for ln in capsys.readouterr().out.splitlines() if "by scope" in ln]
    assert lines == ["bench: device time by scope (share of busy): "
                     f"layer.ffn 75.00%, {UNSCOPED} 25.00%"]


@pytest.mark.parametrize("name", ["ffn_mfu", "attn_proj_mfu", "prefill_glue_us_per_token"])
@pytest.mark.parametrize("case", ["no scopes", "scope not in trace", "no tokens", "engine facts"])
def test_prefill_scope_readers_none(name, case):
    ops = {"fusion.103": 3e6, "fusion.102": 1e6, "convert_bitcast_fusion.4": 1e6,
           "add_rsqrt_fusion.5": 1e6, "copy.20": 1e6}
    if case == "scope not in trace":
        ops = {"flash_folded.6": 3e6, "copy.20": 1e6}
    s, facts = _prefill(ops, lens=() if case == "no tokens" else (1024,))
    if case == "no scopes":  # a program without named scopes: all unscoped
        facts["op_scopes"] = dict.fromkeys(PREFILL_MAP, UNSCOPED)
    if case == "engine facts":  # not a prefill run
        del facts["op_scopes"], facts["cfg"]
        facts.update(m=2, n=32)
    assert _reader(name).read(s, facts, V5E) is None


def _engine(launches=4, names=("pad", "copy.1", "engine_ca_hmap.1", "slice.0")):
    ops, t = [], 0.0
    for _ in range(4):
        for name, d in zip(names, (2e6, 1e6, 5e6, 2e6)):
            pallas = name.startswith("engine_")
            ops.append(trace.Op(name + (" (pallas)" if pallas else ""), t, t + d, pallas))
            t += d
    s = trace.TraceSummary(window=(0, t), device_ops=[ops])
    facts = {"m": 2, "n": 2048, "itemsize": 4, "launches": launches, "window_s": t / 1e9,
             "compare_launches": 0,
             "op_scopes": {"pad": "engine.pad", "copy.1": "engine.pad",
                           "engine_ca_hmap.1": "engine.kernel", "slice.0": "engine.unpad"}}
    return s, facts


def test_engine_copy_reader():
    s, facts = _engine()
    assert _reader("engine_copy_ms").read(s, facts, V5E) == pytest.approx(5.0)


@pytest.mark.parametrize("case", ["no scopes", "no launches", "no copies in trace"])
def test_engine_copy_reader_none(case):
    s, facts = _engine(launches=0 if case == "no launches" else 4)
    if case == "no scopes":
        facts["op_scopes"] = dict.fromkeys(facts["op_scopes"], UNSCOPED)
    if case == "no copies in trace":
        s, _ = _engine(names=("fusion.1", "fusion.2", "engine_ca_hmap.1", "fusion.3"))
    assert _reader("engine_copy_ms").read(s, facts, V5E) is None


# ------------------------------------------------------- the rebuilt step
def test_engine_body_from_the_kernel_name():
    def one(name):
        return trace.TraceSummary(window=(0, 1), device_ops=[[trace.Op(name, 0, 1, True)]])

    assert steps.engine_body(one("engine_ca_hmap.1 (pallas)")) == "ca"
    assert steps.engine_body(one("engine_accum_auto.1 (pallas)")) == "accum"
    assert steps.engine_body(one("closed_call.10 (pallas)")) is None
    assert steps.compiled_hlo(one("closed_call.10 (pallas)"), {"m": 2, "n": 32}) is None
    assert steps.compiled_hlo(one("x"), {"launches": 1}) is None


def _driver(workload, overrides):
    spec = harness.load_cell(workload)
    spec["config"].update(overrides["config"])
    spec["mix"].update(overrides.get("mix", {}))
    mod = harness.load_module(ROOT / "bench" / "drivers" / f"{spec['mix']['driver']}.py")
    d = mod.Driver(spec["config"], spec["mix"], 5, spec["config_dir"], harness.Spans())
    d.setup(False)
    return d, spec["config"]


def test_rebuilt_prefill_step_is_the_drivers():
    d, cfg = _driver("yi6b.prefill_1k", {"config": TINY_YI, "mix": {"prompt_lens": [32]}})
    tokens = jax.ShapeDtypeStruct((1, 32), jnp.int32)
    want = d.step.lower(d.params, tokens).as_text()
    step, params = steps.prefill_step(cfg)
    assert step.lower(params, tokens).as_text() == want
    got = scopes.op_scopes(steps.prefill_hlo(cfg, [(1, 32)]))
    assert {"layer.ffn", "layer.attn_qkv", "layer.attn_out", "layer.norm",
            "model.embed"} <= set(got.values())


@pytest.mark.parametrize("workload, body", [("m2.ca", "ca"), ("m2.accum", "accum")])
def test_rebuilt_engine_step_is_the_drivers(workload, body):
    d, _ = _driver(workload, {"config": {"n": 32}})
    state = jax.ShapeDtypeStruct((32, 32), jnp.int32)
    assert steps.engine_step(body).lower(state).as_text() == d.step.lower(state).as_text()
    got = set(scopes.op_scopes(steps.engine_hlo(body, 2, 32)).values())
    assert {"engine.pad", "engine.kernel", "engine.unpad"} <= got


# ------------------------------------------------- recorded on a TPU v5e
@pytest.fixture(scope="module")
def ca_scopes():
    """CA at n = 2048 on a v5e: a few launches of the production step, and its HLO."""
    s = trace.reduce_trace(TESTDATA / "engine_ca_scopes_window.xplane.pb")
    text = (TESTDATA / "engine_ca_scopes_step.hlo.txt").read_text()
    return s, scopes.op_scopes(text)


def test_recorded_ca_window_by_scope(ca_scopes):
    s, op_map = ca_scopes
    names = {o.name.removesuffix(" (pallas)") for o in s.ops(chip=0)}
    assert {op_map[n] for n in names if n in ("pad", "copy.1")} == {"engine.pad"}
    assert op_map["slice.0"] == "engine.unpad"
    kernels = {o.name for o in s.ops(chip=0) if o.mosaic}
    assert kernels == {"engine_ca_hmap.1 (pallas)"}
    assert op_map["engine_ca_hmap.1"] == "engine.kernel"
    by_scope = scopes.scope_self_ns(s, op_map)
    busy = sum(by_scope.values())
    assert busy == pytest.approx(s.busy_s * 1e9, rel=1e-6)
    assert by_scope.get(UNSCOPED, 0.0) < 0.01 * busy
    assert by_scope["engine.pad"] > 0 and by_scope["engine.unpad"] > 0
    assert by_scope["engine.kernel"] > by_scope["engine.unpad"]
