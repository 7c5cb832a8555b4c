"""The cell's timed step, rebuilt from a traced run's facts, as compiled HLO text.

``bench.core.scopes`` needs the compiled text of the step the window ran.
The drivers keep no copy of it, so the step is built again here as the
driver builds it: the same function under the same name, lowered for
the same shapes on the default device. The persistent compilation cache
keys a program without its debug information, so the compile finds the
executable the window ran and returns its text, op metadata included.
A step that differs would still compile, only slower.

Only traced runs call this, after the window and the check, so the
untraced runs that give the end-to-end metrics do nothing new.
"""

from __future__ import annotations

import sys
import traceback

import jax
import jax.numpy as jnp

__all__ = ["compiled_hlo", "engine_body", "engine_hlo", "engine_step",
           "prefill_hlo", "prefill_step"]

DOMAIN_BODIES = ("accum", "ca")  # bodies whose state is a (n,)*m int32 array


def prefill_step(cfg: dict):
    """``bench/drivers/prefill.py``'s step for ``cfg``, jitted, and its parameters' shapes."""
    from bench.drivers.prefill import program_config
    from repro.models.model import Model

    model = Model(program_config(cfg))

    def prefill_step(params, tokens):
        logits, caches = model.prefill(params, {"tokens": tokens})
        last = logits[:, -1]
        return jnp.argmax(last, -1).astype(jnp.int32), last, caches

    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                          jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    return jax.jit(prefill_step), params


def prefill_hlo(cfg: dict, shapes) -> str:
    """Compiled text of the prefill step at each ``(batch, length)``.

    Instruction names are unique within one module only; with more than one
    shape, a name two modules share keeps the scope the last one gives it.
    """
    step, params = prefill_step(cfg)
    return "\n".join(
        step.lower(params, jax.ShapeDtypeStruct((b, s), jnp.int32)).compile().as_text()
        for b, s in sorted(set(shapes)))


def engine_body(summary) -> str | None:
    """The engine body a window launched, from its Pallas kernel's name.

    The kernel is ``engine_<body>_<kind>`` (its ``name=``), or the jitted
    step's name ``engine_<body>_auto`` where the kernel carries none.
    """
    from repro.kernels.engine import registered_bodies

    for op in summary.ops(chip=0):
        if op.mosaic:
            for body in registered_bodies():
                if op.name.startswith(f"engine_{body}_"):
                    return body
    return None


def engine_step(body: str):
    """``bench/drivers/engine.py``'s step for ``body``, jitted."""
    from repro.kernels import engine

    fn = getattr(engine, body)

    def engine_step(state):
        return fn(state, kind="auto")

    engine_step.__name__ = f"engine_{body}_auto"
    return jax.jit(engine_step)


def engine_hlo(body: str, m: int, n: int) -> str:
    """Compiled text of the engine step for ``body`` on an ``(n,)*m`` int32 state."""
    state = jax.ShapeDtypeStruct((n,) * m, jnp.int32)
    return engine_step(body).lower(state).compile().as_text()


def compiled_hlo(summary, facts: dict) -> str | None:
    """The compiled text of the step a traced run's window ran, or None.

    None when the facts name no step this module can rebuild, or when the
    rebuild fails (said on standard error): a per-layer reader then
    reports nothing rather than stopping the run.
    """
    try:
        if facts.get("cfg") and facts.get("prompt_lens"):
            shapes = {(facts["batch"], s) for s in facts["prompt_lens"]}
            return prefill_hlo(facts["cfg"], shapes)
        if "m" in facts and "n" in facts:
            body = engine_body(summary)
            if body in DOMAIN_BODIES:
                return engine_hlo(body, int(facts["m"]), int(facts["n"]))
    except Exception:  # noqa: BLE001 - a reader reports nothing, never raises
        print("bench: the step's HLO could not be rebuilt:", file=sys.stderr)
        traceback.print_exc(file=sys.stderr)
    return None
