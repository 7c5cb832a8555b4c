"""Compile the main path's kernels for a described TPU v5e, no chip needed.

The TPU compiler is installed beside JAX; it compiles for a chip that is
described (``v5e:2x2``) and not attached, and refuses what the chip
would refuse: unaligned tiles, too much VMEM, unsupported Mosaic ops.
Every kernel here must lower to a ``tpu_custom_call`` at its real
width.  The topology is described inside a fixture, never at import,
so every test worker collects the same tests.  Each test steers the
kernel to the compiled path itself (``interpret=False`` and an explicit
tile), since the process's own backend is the CPU.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import engine
from repro.kernels.flash_attention import flash_attention
from repro.models.attention import decode_attention

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
