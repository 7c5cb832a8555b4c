"""Driver family: the simplex engine, one body launched back to back.

The state is drawn from the seed on the device. Each launch goes through
the program's public entry (``repro.kernels.engine.<body>(state,
kind="auto")``), jitted once, and takes the previous launch's output as
its input. The host blocks once per chunk of launches sized to about a
quarter of a second, so the device queue never runs dry.
"""

from __future__ import annotations

import gc
import statistics
import time

import jax
import numpy as np

from bench.core.counts import simplex_elements
from bench.core.harness import load_module, seed_key
from bench.core.traffic import rng

__all__ = ["Driver"]

CHUNK_S = 0.25


class Driver:
    """Back-to-back launches of one engine body on one configuration.

    Args:
        cfg: The configuration file's contents.
        mix: The traffic mix.
        seed: The run's seed.
        config_dir: Where the configuration's reference module lives.
        spans: The harness's span factory.
        step_wrapper: Tests only: wraps the compiled step, to plant a fault.
        control: Check the control (the reference's ``control_`` form of
            the body) in the program's place, on the same inputs.
    """

    def __init__(self, cfg, mix, seed, config_dir, spans, step_wrapper=None,
                 control=False):
        self.cfg, self.mix, self.seed, self.spans = cfg, mix, int(seed), spans
        self.ref = load_module(config_dir / cfg["reference"])
        self.m, self.n = int(cfg["m"]), int(cfg["n"])
        self.body = mix["body"]
        self.step_wrapper = step_wrapper
        self.control = bool(control)
        self.attempted = self.failed = 0
        self._notes = []

    def _entry(self, kind: str):
        from repro.kernels import engine

        fn = getattr(engine, self.body)

        def engine_step(state):
            return fn(state, kind=kind)

        engine_step.__name__ = f"engine_{self.body}_{kind}"
        return jax.jit(engine_step)

    def setup(self, trace: bool = False) -> None:
        """State on the device, the launch compiled and timed."""
        from repro.core.schedule import resolve_kind
        from repro.kernels.engine import default_rho, grid_steps

        rho = default_rho(self.m)
        nb = self.n // rho
        self._notes.append(
            f"engine {self.body} at m={self.m}, n={self.n}: rho {rho}, kind "
            f"auto -> {resolve_kind(self.m, nb, 'auto')} "
            f"({grid_steps(nb, 'auto', self.m)} grid steps; "
            f"{self.mix['compare_kind']}: "
            f"{grid_steps(nb, self.mix['compare_kind'], self.m)})")
        self.key = seed_key(self.seed)
        make = jax.jit(self.ref.initial_state, static_argnums=(1, 2, 3))
        self._make = lambda: make(self.key, self.n, self.mix["init"],
                                  int(self.mix.get("init_high", 0)))
        step = self._entry("auto")
        self.step = self.step_wrapper(step) if self.step_wrapper else step
        self.compare = self._entry(self.mix["compare_kind"]) if trace else None
        x = self._make()
        for f in (self.step, self.compare):
            if f is not None:
                f(f(x)).block_until_ready()
        t = time.perf_counter()
        y = x
        for _ in range(3):
            y = self.step(y)
        y.block_until_ready()
        per = (time.perf_counter() - t) / 3
        self.chunk = max(1, int(CHUNK_S / per))
        self.per_launch_s = per

    def run_window(self, seconds: float) -> None:
        """Launch in chunks until ``seconds`` have passed; keep what the check reads."""
        spans = self.spans
        est = max(2, int(0.8 * seconds / self.per_launch_s))
        stepwise = hasattr(self.ref, f"{self.body}_step")
        picks = {0} | {int(i) for i in rng(self.seed, 3).integers(
            1, est, size=int(self.mix["check_samples"]) - 2)} if stepwise else set()
        self.kept = {}
        state = self.x0 = self._make()
        state.block_until_ready()
        g = 0
        self.chunk_s = []
        t0 = now = time.perf_counter()
        while True:
            with spans("dispatch"):
                for _ in range(self.chunk):
                    prev, state = state, self.step(state)
                    if g in picks:
                        self.kept[g] = (prev, state)
                    g += 1
            with spans("wait"):
                state.block_until_ready()
            self.chunk_s.append(time.perf_counter() - now)
            now = time.perf_counter()
            if now - t0 >= seconds:
                break
        self.window_s = now - t0
        if stepwise:
            self.kept[g - 1] = (prev, state)
        self.launches = self.attempted = g
        self.final = state

    def trace_extra(self) -> None:
        """A few launches of the comparison kind, for the per-layer ratio."""
        s = self.final
        for _ in range(3):
            s = self.compare(s)
        s.block_until_ready()
        self.compare_launches = 3

    def end_to_end(self) -> dict:
        """``elements_per_s``: domain elements updated per second of window."""
        return {"elements_per_s":
                self.launches * simplex_elements(self.m, self.n) / self.window_s}

    def facts(self) -> dict:
        """What the per-layer readers count from."""
        return {"m": self.m, "n": self.n, "itemsize": 4,
                "launches": self.launches, "window_s": self.window_s,
                "compare_launches": getattr(self, "compare_launches", 0)}

    def notes(self) -> list:
        """Earlier lines: the resolved schedule and the launch count."""
        return self._notes + [
            f"{self.launches} launches in {self.window_s:.3f} s (chunks of "
            f"{self.chunk}: median {statistics.median(self.chunk_s):.4f} s, "
            f"slowest {sorted(self.chunk_s)[-3:]} s)"]

    def release(self) -> None:
        """Drop the compiled launches (the kept states stay for the check)."""
        del self.step, self.compare
        gc.collect()

    def check(self) -> list:
        """Every element of the checked states against the reference.

        A body the reference steps (``<body>_step``) is checked on the kept
        launches, each output against one step of its input; any other on
        the final state, against ``<body>_after`` the window's launches. In
        control mode the reference's ``control_`` form of the same function
        gives the outputs in the program's place.
        """
        ref, body = self.ref, self.body
        if not self.kept:
            n = np.int32(self.launches)
            want = getattr(ref, f"{body}_after")(self.x0, n)
            got = (getattr(ref, f"control_{body}_after")(self.x0, n) if self.control
                   else self.final)
            bad = int(ref.mismatches(got, want))
        else:
            step = getattr(ref, f"{body}_step")
            low = getattr(ref, f"control_{body}_step") if self.control else None
            bad = 0
            for g in sorted(self.kept):
                inp, out = self.kept[g]
                got = low(inp) if self.control else out
                bad += int(ref.mismatches(got, step(inp)))
        return [{"name": "mismatched_elements", "value": bad,
                 "limit": self.cfg["limits"]["mismatched_elements"]}]
