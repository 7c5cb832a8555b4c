"""Smoke run of the main paths on a TPU, through their user entry points.

One chip (no arguments):

1. attention — folded flash and chunked XLA causal attention at yi-6b
   widths, (4, 32, 2048, 128) bf16 with 4 kv heads, against a float32
   reference;
2. engine — the simplex engine's ACCUM, EDM and CA bodies at m = 2 on a
   16384^2 domain (1 GiB of int32) and ACCUM at m = 3 on 512^3, each
   under the H map ('hmap') and the bounding box ('bb'), element for
   element against the oracles in ``kernels/ref.py``;
3. serve — ``repro.launch.serve.main`` on yi-6b at its published widths
   and all 32 layers in bf16: batch 4, prompt 2048, 16 decode steps.

Four chips (``--chips 4``): only the sharded m = 2 CA over a 4-device
mesh, both executors, 8 generations, against the one-chip engine and
the oracle.

  python chip_smoke.py
  python chip_smoke.py --chips 4

Earlier lines name each phase, its sizes, compile time and the
tolerances met; the last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``
and is printed only when every phase passed.  Without a TPU, with
Pallas forced into interpret mode, or with any phase failing, the exit
code is 1 and no such line is printed.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
import traceback

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
if os.path.isdir(_SRC) and _SRC not in sys.path:
    sys.path.insert(0, _SRC)

# Attention against the float32 reference, elementwise
# |x - ref| <= ATTN_ATOL + ATTN_RTOL * |ref|.  Inputs are N(0, 1) bf16,
# so outputs reach about 5; rounding the output to bf16 alone costs up
# to 2^-9 relative, and the f32-accumulated scores and bf16 probabilities
# add well under 1e-2 absolute.
ATTN_ATOL = 2.0**-6
ATTN_RTOL = 2.0**-7
# EDM (float32) against the XLA oracle: the kernel and XLA may take
# different sqrt and reduction sequences, a few ulp apart.
EDM_RTOL = 1e-5
EDM_ATOL = 1e-5
EDM_POINT_DIM = 2
# First decode step against the last position of a prefill over s + 1
# tokens, 32 bf16 layers: relative L2 and relative max error of the
# logits.  Bf16 rounding of each layer's activations walks the two
# paths apart: 1.0% at 8 and 1.7% at 32 layers of a d_model 1024 cut
# on the CPU.  A broken cache or position gives errors of order 1.
DECODE_REL_TOL = 0.1

SERVE_ARGS = dict(arch="yi-6b", batch=4, prompt_len=2048, gen=16)
ATTN_SHAPE = dict(b=4, hq=32, hkv=4, s=2048, d=128)
ENGINE_CASES = (
    ("accum", 2, 16384),
    ("edm", 2, 16384),
    ("ca", 2, 16384),
    ("accum", 3, 512),
)
SHARDED_CA = dict(m=2, n=2048, k=4, gens=8)


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _compiled() -> bool:
    """True when Pallas kernels compile (not interpret) on this backend."""
    from repro.kernels.policy import default_interpret

    return not default_interpret()


def _compile(fn, *args):
    """(compiled executable, its HLO text, seconds to compile)."""
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    return compiled, compiled.as_text(), time.perf_counter() - t0


def preflight(platform: str, interpret: bool) -> str | None:
    """Why this process cannot run the smoke phases, or None.

    Args:
        platform: ``jax.devices()[0].platform``.
        interpret: The Pallas policy's resolved interpret mode.

    Returns:
        An error message, or None when the run may go ahead.
    """
    if platform != "tpu":
        return f"needs a TPU; JAX found platform {platform!r}"
    if interpret:
        return "REPRO_INTERPRET forces Pallas interpret mode on the TPU"
    return None


def _within(x, ref, atol, rtol):
    import numpy as np

    x = np.asarray(x, np.float32)
    ref = np.asarray(ref, np.float32)
    err = np.abs(x - ref)
    ok = bool(np.all(err <= atol + rtol * np.abs(ref)))
    return ok, float(err.max()), float(err.mean())


# ---------------------------------------------------------------------------
# phases: each returns one summary line, raises on failure
# ---------------------------------------------------------------------------


def phase_attention(b: int, hq: int, hkv: int, s: int, d: int,
                    seed: int = 0) -> str:
    """Folded flash and chunked attention against the float32 reference."""
    import jax
    import jax.numpy as jnp

    from repro.autotune import choose_attn_impl
    from repro.kernels.flash_attention import _reference_attention
    from repro.models.attention import simplex_attention

    dec = choose_attn_impl(s, hq, d)
    _check(dec.impl == "flash" and dec.kind == "folded",
           f"attention decision is {dec.impl}/{dec.kind}, not flash/folded")
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, hkv, s, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, hkv, s, d), jnp.bfloat16)
    flash, hlo, compile_s = _compile(
        jax.jit(functools.partial(simplex_attention, impl="flash-folded")),
        q, k, v,
    )
    chunked = jax.jit(functools.partial(simplex_attention, impl="chunked"))
    if _compiled():
        _check("tpu_custom_call" in hlo, "flash HLO has no tpu_custom_call")

    def reference(q, k, v):
        f32 = jnp.float32
        return _reference_attention(
            q.astype(f32), k.astype(f32), v.astype(f32), None, None,
            d**-0.5,
        )

    with jax.default_matmul_precision("highest"):
        want = jax.jit(reference)(q, k, v)
    parts = [f"decision flash/folded block {dec.block_q}",
             f"flash compile {compile_s:.1f}s"]
    for name, fn in (("flash-folded", flash), ("chunked", chunked)):
        ok, mx, mean = _within(fn(q, k, v), want, ATTN_ATOL, ATTN_RTOL)
        _check(ok, f"{name} vs f32 reference: max err {mx:.3e} over "
                   f"atol {ATTN_ATOL:g} + rtol {ATTN_RTOL:g}")
        parts.append(f"{name} max err {mx:.3e} mean {mean:.2e}")
    return (f"q ({b},{hq},{s},{d}) kv heads {hkv} bf16: " + "; ".join(parts)
            + f" (tol atol {ATTN_ATOL:g} + rtol {ATTN_RTOL:g}*|ref|)")


def _engine_case(body: str, m: int, n: int, seed: int):
    """(operand, oracle, launcher) for one engine body."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import engine, ref

    key = jax.random.PRNGKey(seed)
    if body == "edm":
        x = jax.random.normal(key, (n, EDM_POINT_DIM), jnp.float32)
        oracle = ref.edm2d if m == 2 else functools.partial(ref.edm_md, m=m)
        return x, jax.jit(oracle)(x), functools.partial(engine.edm, m=m)
    hi = 2 if body == "ca" else 1000
    x = jax.random.randint(key, (n,) * m, 0, hi, jnp.int32)
    if body == "accum":
        step, launch = ref.accum_md, engine.accum
    else:
        step = ref.ca2d_step if m == 2 else ref.ca_md_step
        launch = engine.ca
    # the engine leaves off-domain elements as they were
    want = jax.jit(
        lambda x: jnp.where(ref.simplex_mask(m, n), step(x), x)
    )(x)
    return x, want, launch


def phase_engine(body: str, m: int, n: int, kinds=("hmap", "bb"),
                 seed: int = 0) -> str:
    """One engine body under each kind, against its oracle and each other."""
    import jax
    import numpy as np

    from repro.kernels import engine

    x, want, launch = _engine_case(body, m, n, seed)
    want = np.asarray(want)
    rho = engine.SimplexKernel(body, m).rho
    outs, parts = {}, []
    for kind in kinds:
        fn, hlo, compile_s = _compile(
            jax.jit(functools.partial(launch, kind=kind)), x
        )
        if _compiled():
            _check("tpu_custom_call" in hlo,
                   f"{body} m={m} {kind}: no tpu_custom_call")
        got = np.asarray(fn(x))
        if body == "edm":
            ok, mx, _ = _within(got, want, EDM_ATOL, EDM_RTOL)
            _check(ok, f"edm m={m} {kind}: max err {mx:.3e}")
            err = f"max err {mx:.3e}"
        else:
            bad = int(np.count_nonzero(got != want))
            _check(bad == 0, f"{body} m={m} {kind}: {bad} elements differ")
            err = "exact"
        steps = engine.grid_steps(n // rho, kind, m)
        parts.append(f"{kind} {steps} steps compile {compile_s:.1f}s {err}")
        outs[kind] = got
    first = outs[kinds[0]]
    for kind in kinds[1:]:
        _check(np.array_equal(first, outs[kind]),
               f"{body} m={m}: {kinds[0]} != {kind}")
    tol = (f" (tol atol {EDM_ATOL:g} + rtol {EDM_RTOL:g}*|ref|)"
           if body == "edm" else "")
    return (f"{body} m={m} n={n} rho={rho}: " + "; ".join(parts)
            + f"; {' == '.join(kinds)} bitwise" + tol)


def phase_serve(arch: str, batch: int, prompt_len: int, gen: int,
                smoke: bool = False, seed: int = 0) -> str:
    """The serve entry point, then its first decode step vs a prefill."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import get_config
    from repro.launch import serve
    from repro.models.model import Model

    argv = ["--arch", arch, "--batch", str(batch), "--prompt-len",
            str(prompt_len), "--gen", str(gen), "--seed", str(seed)]
    run = serve.main(argv + (["--smoke"] if smoke else []))
    cfg = run.model.cfg
    _check((run.attn.impl, run.attn.kind) == ("flash", "folded"),
           f"serve attention decision is {run.attn.impl}/{run.attn.kind}")
    if _compiled():
        _check("tpu_custom_call" in run.prefill_hlo,
               "prefill HLO has no flash tpu_custom_call")
    for name, lg in (("prefill", run.prefill_logits),
                     ("decode", run.first_decode_logits)):
        _check(bool(jnp.all(jnp.isfinite(lg))), f"{name} logits not finite")

    # the reference: one prefill over the prompt plus the fed token,
    # attention as a single chunked tile (s + 1 has no flash tiling)
    ref_model = Model(cfg.replace(attention_impl="chunked",
                                  attention_chunk=prompt_len + 1))
    tokens = jnp.concatenate(
        [run.tokens, jnp.asarray(run.generated[:, :1], jnp.int32)], axis=1
    )
    want = jax.jit(lambda p, t: ref_model.prefill(p, {"tokens": t})[0])(
        run.params, tokens
    )
    a = np.asarray(run.first_decode_logits[:, -1], np.float32)
    r = np.asarray(want[:, -1], np.float32)
    rel_l2 = float(np.linalg.norm(a - r) / np.linalg.norm(r))
    rel_max = float(np.abs(a - r).max() / np.abs(r).max())
    _check(rel_l2 <= DECODE_REL_TOL and rel_max <= DECODE_REL_TOL,
           f"first decode step vs prefill(s+1): rel L2 {rel_l2:.3e}, "
           f"rel max {rel_max:.3e} > {DECODE_REL_TOL:g}")
    return (
        f"{cfg.name} {cfg.n_layers} of {get_config(arch).n_layers} layers "
        f"{cfg.param_dtype}, batch {batch} prompt {prompt_len} gen {gen}: "
        f"attention flash/folded, flash tpu_custom_call in prefill: "
        f"{'tpu_custom_call' in run.prefill_hlo}, logits finite, compile "
        f"{run.compile_s:.1f}s, prefill {run.prefill_s:.3f}s, decode "
        f"{run.decode_s:.3f}s (smoke timings, not metrics); first decode "
        f"step vs prefill(s+1) rel L2 {rel_l2:.3e} rel max {rel_max:.3e} "
        f"(tol {DECODE_REL_TOL:g})"
    )


def phase_sharded_ca(m: int, n: int, k: int, gens: int,
                     seed: int = 0) -> str:
    """Sharded CA, both executors, vs the one-chip engine and the oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.distributed.simplex_sharding import (
        ShardedSimplexCA,
        shard_mesh,
    )
    from repro.kernels import engine, ref

    mesh = shard_mesh(k)
    runner = ShardedSimplexCA(m, n, k, kind="hmap", mesh=mesh)
    x0 = jax.random.randint(jax.random.PRNGKey(seed), (n,) * m, 0, 2,
                            jnp.int32)
    placed = [sorted(str(d) for d in y.devices())
              for y in runner.shard_outputs(x0)]
    print(f"  shard outputs on devices: {placed}", flush=True)
    step = ref.ca2d_step if m == 2 else ref.ca_md_step
    single = jax.jit(functools.partial(engine.ca, kind="hmap"))
    oracle_step = jax.jit(
        lambda s, x0: jnp.where(ref.simplex_mask(m, n), step(s), x0)
    )
    one, oracle = x0, x0
    for _ in range(gens):
        one = single(one)
        oracle = oracle_step(oracle, x0)
    one, oracle = np.asarray(one), np.asarray(oracle)
    _check(np.array_equal(one, oracle), "one-chip engine != oracle")
    parts = []
    for executor in ("spmd", "engine"):
        out = runner.run(x0, gens, executor=executor)
        where = sorted(str(d) for d in out.devices())
        _check(np.array_equal(np.asarray(out), one),
               f"{executor} executor != one-chip engine")
        parts.append(f"{executor} bit-exact (result on {where})")
    return (f"m={m} n={n} k={k} rho={runner.rho} gens={gens}: "
            + "; ".join(parts) + "; one-chip engine == oracle")


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def _phases(chips: int):
    if chips == 4:
        return [("sharded-ca", functools.partial(phase_sharded_ca,
                                                 **SHARDED_CA))]
    phases = [("attention", functools.partial(phase_attention, **ATTN_SHAPE))]
    for body, m, n in ENGINE_CASES:
        phases.append((f"engine-{body}-m{m}",
                       functools.partial(phase_engine, body, m, n)))
    phases.append(("serve-yi-6b", functools.partial(phase_serve,
                                                    **SERVE_ARGS)))
    return phases


def main(argv=None) -> int:
    """Run the phases; print the result line only if all of them pass.

    Args:
        argv: Command-line arguments (None = ``sys.argv``).

    Returns:
        Process exit code: 0 when every phase passed, else 1.
    """
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded CA across four chips")
    args = ap.parse_args(argv)

    import jax

    from repro.kernels.policy import default_interpret

    devices = jax.devices()
    why = preflight(devices[0].platform, default_interpret())
    if why is None and len(devices) < args.chips:
        why = f"--chips {args.chips} needs {args.chips} devices, found {len(devices)}"
    if why is not None:
        print(f"chip_smoke: {why}", file=sys.stderr)
        return 1

    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    print(f"device {devices[0].device_kind} x{len(devices)}, jax "
          f"{jax.__version__}, compile cache "
          f"{cache or os.environ.get('JAX_COMPILATION_CACHE_DIR')}",
          flush=True)
    ok = True
    for name, phase in _phases(args.chips):
        print(f"phase {name} ...", flush=True)
        t0 = time.perf_counter()
        try:
            summary = phase()
        except Exception:
            traceback.print_exc()
            print(f"FAIL {name} ({time.perf_counter() - t0:.1f}s)",
                  flush=True)
            ok = False
            continue
        print(f"PASS {name} ({time.perf_counter() - t0:.1f}s): {summary}",
              flush=True)
        stats = devices[0].memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            print(f"  device memory peak so far "
                  f"{stats['peak_bytes_in_use'] / 1e9:.2f} GB of "
                  f"{stats.get('bytes_limit', 0) / 1e9:.2f} GB", flush=True)
    if not ok:
        return 1
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
