"""One run of one cell: set-up, the measured window, the check, the result line.

The harness knows no configuration, mix or metric by name. It reads the
cell from ``BENCHMARK.json``, loads the configuration's file from
``bench/configs``, the mix from ``bench/traffic`` and the driver the mix
names from ``bench/drivers``, and, in a traced run, one reader per
per-layer metric from ``bench/metrics``. Adding a cell or a metric adds
files and entries; it edits none of these.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import time
from contextlib import contextmanager
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".bench_cache"

__all__ = ["BENCH", "ROOT", "Spans", "CompileCounter", "seed_key",
           "load_module", "load_cell", "configure_jax", "run_cell",
           "result_line", "process_start_time"]


def process_start_time() -> float:
    """Wall-clock time at which this process started (Linux), else now."""
    try:
        ticks = int(Path("/proc/self/stat").read_text().rsplit(")", 1)[1].split()[19])
        uptime = float(Path("/proc/uptime").read_text().split()[0])
        return time.time() - uptime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.time()


def load_module(path: Path):
    """Import a file of the benchmark by its path (names may hold '-' or '.')."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, spec_path: Path = ROOT / "BENCHMARK.json") -> dict:
    """The cell ``name`` with its configuration, mix and metric entries."""
    spec = json.loads(Path(spec_path).read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {spec_path}; "
                       f"known: {sorted(cells)}")
    cell = cells[name]
    config = {c["name"]: c for c in spec["configs"]}[cell["config"]]

    def mine(metric):
        return name in metric.get("workloads", [name])

    from .traffic import load_mix

    return {
        "cell": cell,
        "config": json.loads((ROOT / config["file"]).read_text()),
        "config_dir": (ROOT / config["file"]).parent,
        "mix": load_mix(BENCH / "traffic" / f"{cell['traffic']}.json"),
        "end_to_end": [m for m in spec["end_to_end"] if mine(m)],
        "per_layer": [m for m in spec["per_layer"] if mine(m)],
    }


def seed_key(seed: int):
    """A JAX key from a seed of up to 64 bits."""
    import jax
    import numpy as np

    seed = int(seed)
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32((seed >> 32) & 0xFFFFFFFF))


class Spans:
    """The benchmark's own host spans, written into the profiler's trace.

    ``TraceAnnotation`` costs next to nothing when no trace is taken, so
    the same code runs in both kinds of run.
    """

    @contextmanager
    def __call__(self, name: str):
        from jax.profiler import TraceAnnotation

        with TraceAnnotation("bench." + name):
            yield


class CompileCounter:
    """Counts JAX traces and backend compiles while ``active``."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/backend_compile_duration": "compiles"}

    def __init__(self):
        import jax

        self.active = False
        self.counts = {"traces": 0, "compiles": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kwargs):
        if self.active and event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1


def configure_jax() -> None:
    """The persistent compilation cache in the checkout, every program in it."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR / "jax")
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def _start_trace(path: Path) -> None:
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the bench.* spans stay; Python calls cost too much
    options.enable_hlo_proto = False
    jax.profiler.start_trace(str(path), profiler_options=options)


def _say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def result_line(correct, attempted, failed, metrics, device, checks,
                breakdown=None) -> str:
    """The last line of standard output; the compared numbers come last."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in checks}
    return json.dumps(out)


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, *, overrides: dict | None = None,
             require_chip: bool = True) -> int:
    """Run ``workload`` once and print its result line; the exit code.

    Args:
        workload: The cell's name in ``BENCHMARK.json``.
        seed: Seeds the inputs and the weights.
        seconds: Length of the measured window.
        trace: Take a profiler trace of the window and report the
            cell's per-layer metrics instead of its end-to-end ones.
        t_start: Process start, wall clock; set-up runs from here.
        overrides: Tests and ``bench/calibrate.py`` only: ``config`` and
            ``mix`` entries to replace (sizes a CPU can hold) and ``driver``
            keyword arguments (``step_wrapper`` plants a fault, ``control``
            checks the control in the program's place).
        require_chip: Refuse to run without a TPU (tests turn it off).
    """
    spec = load_cell(workload)
    overrides = overrides or {}
    spec["config"].update(overrides.get("config", {}))
    spec["mix"].update(overrides.get("mix", {}))
    configure_jax()
    import jax

    sys.path.insert(0, str(ROOT / "src"))
    from repro.kernels.policy import default_interpret

    from . import device as dev

    devices = jax.devices()
    if require_chip:
        why = dev.refuse_reason(devices, spec["cell"]["chips"],
                                default_interpret())
        if why:
            _say(f"bench: {why}; no result")
            return 3
    spans = Spans()
    counter = CompileCounter()
    mod = load_module(BENCH / "drivers" / f"{spec['mix']['driver']}.py")
    driver = mod.Driver(spec["config"], spec["mix"], seed, spec["config_dir"],
                        spans, **overrides.get("driver", {}))
    driver.setup(trace)

    trace_dir = CACHE_DIR / "trace" / workload
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        _start_trace(trace_dir / "window")
    setup_s = time.time() - t_start
    counter.active = True
    with spans("window"):
        driver.run_window(seconds)
    counter.active = False
    if trace:
        jax.profiler.stop_trace()
        _start_trace(trace_dir / "compare")
        with spans("compare"):
            driver.trace_extra()
        jax.profiler.stop_trace()
    print(f"bench: set-up {setup_s:.3f} s; in the window "
          f"{counter.counts['traces']} traces, {counter.counts['compiles']} "
          "compiles", flush=True)
    for line in driver.notes():
        print(f"bench: {line}", flush=True)

    device = dev.describe(devices)
    device["memory_peak_bytes"] = dev.memory_peak_bytes(devices)
    e2e = driver.end_to_end()
    facts = driver.facts()
    driver.release()
    t_check = time.time()
    checks = driver.check()
    print(f"bench: check {time.time() - t_check:.3f} s", flush=True)
    correct = bool(checks) and all(
        c["limit"] is not None and c["value"] <= c["limit"] for c in checks)

    metrics, breakdown = {}, None
    if trace:
        from .trace import reduce_trace

        chips = spec["cell"]["chips"]
        summary = reduce_trace(trace_dir / "window", n_devices=chips)
        summary.compare = reduce_trace(trace_dir / "compare", n_devices=chips)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        breakdown = summary.breakdown()
        # a number from a CPU run is never written under a device metric
        per_layer = spec["per_layer"] if require_chip else []
        peaks = dev.peaks_for(device["kind"]) if require_chip else None
        for m in per_layer:
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
            value = reader.read(summary, facts, peaks)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in spec["end_to_end"]:
            value = setup_s if m["name"] == "setup_s" else e2e.get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    for c in checks:
        _say(f"check {c['name']} = {c['value']!r} (limit {c['limit']!r})")
    print(result_line(correct, driver.attempted, driver.failed, metrics,
                      device, checks, breakdown), flush=True)
    return 0
