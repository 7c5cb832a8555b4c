"""Compile the main path's kernels for a described TPU v5e, no chip needed.

The TPU compiler is installed beside JAX; it compiles for a chip that is
described (``v5e:2x2``) and not attached, and refuses what the chip
would refuse: unaligned tiles, too much VMEM, unsupported Mosaic ops.
Every kernel here must lower to a ``tpu_custom_call`` at its real
width.  The topology is described inside a fixture, never at import,
so every test worker collects the same tests.  Each test steers the
kernel to the compiled path itself (``interpret=False`` and an explicit
tile), since the process's own backend is the CPU.  The last tests read
the compiled text's ``op_name`` metadata: every op of the prefill step
and of an engine launch sits in the named scope of its program layer.
"""

import functools
import os
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import engine
from repro.kernels.flash_attention import flash_attention
from repro.models.attention import decode_attention

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench.core.scopes import UNSCOPED, op_scopes  # noqa: E402

# yi-6b attention widths: 32 q / 4 kv heads of 128, batch 4, prompt 2048.
B, HQ, HKV, S, D = 4, 32, 4, 2048, 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topology = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - no TPU compiler to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache
    # but cannot be read back without one: keep the cache off here.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topology
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _compile_text(fn, one_chip, *shapes):
    return _compile(fn, one_chip, *shapes).as_text()


def test_topology_is_v5e(topo):
    assert topo.devices[0].device_kind == "TPU v5 lite"


@pytest.mark.parametrize("kind", ["folded", "bb"])
@pytest.mark.parametrize("s", [S, 1920])  # 1920: an odd tile count
def test_flash_attention_compiles(one_chip, kind, s):
    fn = functools.partial(
        flash_attention, kind=kind, block_q=128, block_kv=128,
        interpret=False,
    )
    q = ((B, HQ, s, D), jnp.bfloat16)
    kv = ((B, HKV, s, D), jnp.bfloat16)
    assert "tpu_custom_call" in _compile_text(fn, one_chip, q, kv, kv)


def test_decode_attention_compiles(one_chip):
    # decode attention is XLA (no Pallas kernel): it must compile and
    # read the cache in place, with no copy of it in temporaries.
    q = ((B, HQ, 1, D), jnp.bfloat16)
    cache = ((B, HKV, S, D), jnp.bfloat16)
    new = ((B, HKV, 1, D), jnp.bfloat16)
    compiled = _compile(decode_attention, one_chip, q, cache, cache, new, new)
    cache_bytes = B * HKV * S * D * 2
    assert compiled.memory_analysis().temp_size_in_bytes < cache_bytes


@pytest.mark.parametrize("kind", ["hmap", "bb"])
@pytest.mark.parametrize("body", ["accum", "edm", "ca"])
def test_engine_m2_compiles(one_chip, body, kind):
    n = 1024
    if body == "edm":
        fn = functools.partial(engine.edm, m=2, rho=128, kind=kind,
                               interpret=False)
        shape = ((n, 2), jnp.float32)
    else:
        launch = engine.accum if body == "accum" else engine.ca
        fn = functools.partial(launch, rho=128, kind=kind, interpret=False)
        shape = ((n, n), jnp.int32)
    assert "tpu_custom_call" in _compile_text(fn, one_chip, shape)


def test_engine_accum_m3_compiles(one_chip):
    # (128,)*3 int32 blocks: 8 MiB each, past Mosaic's 16 MiB scoped
    # default once double-buffered; the engine asks for a larger limit.
    fn = functools.partial(engine.accum, rho=128, kind="hmap",
                           interpret=False)
    text = _compile_text(fn, one_chip, ((512, 512, 512), jnp.int32))
    assert "tpu_custom_call" in text


# ---------------------------------------------------------------------------
# named scopes: every op of a compiled step carries its program layer in its
# op_name metadata; the chip benchmark charges its device time there
# (bench/core/scopes.py), so a matmul outside the matmul scopes, or an engine
# copy outside engine.pad / engine.unpad, would land in the wrong layer.
# ---------------------------------------------------------------------------

MATMUL_SCOPES = {"layer.ffn", "layer.attn_qkv", "layer.attn_out", "model.lm_head"}
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%(\S+) = .*?\s([a-z][\w\-]*)\(")


def _opcodes(text):
    """``{instruction name: opcode}`` of every instruction of ``text``."""
    return {m.group(1): m.group(2)
            for m in map(_INSTR.match, text.splitlines()) if m}


def _kernels(text):
    """Names of the Pallas (Mosaic) custom calls of ``text``."""
    return [m.group(1) for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            and (m := _INSTR.match(line))]


def test_prefill_ops_are_scoped(one_chip, monkeypatch):
    from repro.configs.base import get_config
    from repro.models.model import Model

    monkeypatch.setenv("REPRO_INTERPRET", "0")  # flash on its compiled path
    cfg = get_config("yi-6b").replace(
        n_layers=2, d_model=256, n_heads=2, n_kv_heads=1, head_dim=128,
        d_ff=512, vocab=512, param_dtype="bfloat16", act_dtype="bfloat16",
        remat="none")
    model = Model(cfg)

    def prefill_step(params, tokens):
        logits, caches = model.prefill(params, {"tokens": tokens})
        return jnp.argmax(logits[:, -1], -1), caches

    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((1, 256), jnp.int32, sharding=one_chip)
    text = jax.jit(prefill_step).lower(params, tokens).compile().as_text()
    scopes, opcodes = op_scopes(text), _opcodes(text)
    matmuls = {n: scopes[n] for n, op in opcodes.items()
               if op in ("dot", "convolution")}
    assert set(matmuls.values()) <= MATMUL_SCOPES, matmuls
    # the head at one position is a multiply-reduce, not a dot
    assert {"layer.ffn", "layer.attn_qkv", "layer.attn_out"} <= set(
        matmuls.values())
    kernels = _kernels(text)
    assert [n.split(".")[0] for n in kernels] == ["flash_folded"]
    assert {scopes[n] for n in kernels} == {"layer.attn_core"}
    for scope in ("layer.norm", "layer.rope", "model.embed", "model.lm_head"):
        assert scope in scopes.values()


@pytest.mark.parametrize("kind", ["hmap", "bb"])
@pytest.mark.parametrize("body", ["accum", "ca"])
def test_engine_copies_and_kernel_are_scoped(one_chip, body, kind):
    launch = getattr(engine, body)
    fn = functools.partial(launch, rho=128, kind=kind, interpret=False)
    text = _compile_text(fn, one_chip, ((256, 256), jnp.int32))
    scopes, opcodes = op_scopes(text), _opcodes(text)
    by_op = {}
    for name, op in opcodes.items():
        by_op.setdefault(op, set()).add(scopes[name])
    kernel = f"engine_{body}_{kind}.1"
    assert _kernels(text) == [kernel]
    assert scopes[kernel] == "engine.kernel"
    if kind == "hmap":
        # every step of the walk is valid: no trash tile to append or
        # slice off. Aliasing the kernel's output to the step's operand,
        # which is not donated, leaves one copy of the state; it reads
        # the parameter, which carries no op_name, so only the kernel is
        # held to a scope here.
        assert "pad" not in by_op and "slice" not in by_op
        assert list(opcodes.values()).count("copy") == 1
        return
    assert by_op["pad"] == {"engine.pad"}
    assert by_op["slice"] == {"engine.unpad"}
    # CA's halo reads the state it writes: aliasing it forces a copy
    assert by_op.get("copy", set()) == ({"engine.pad"} if body == "ca" else set())
    assert UNSCOPED not in {scopes[n] for n, op in opcodes.items()
                            if op in ("pad", "slice", "copy", "custom-call")}
