"""Record the scope reduction's test data on a TPU: a traced CA window and its step's HLO.

    python3 bench/record_scopes.py [--n 2048] [--launches 8] [--out DIR]

Runs the m2.ca cell's step (``bench.core.steps.engine_step("ca")``) at a
small ``n``, compiled once, and traces a few launches as a benchmark window
is traced. Writes ``engine_ca_scopes_window.xplane.pb`` (the trace) and
``engine_ca_scopes_step.hlo.txt`` (the executable's HLO text) to ``DIR``
(``bench/testdata``), then prints each scope's share of the window's busy
time. ``tests/chipbench/test_scopes.py`` reduces the pair.
"""

from __future__ import annotations

import argparse
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    """Record the pair; the exit code."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n", type=int, default=2048)
    ap.add_argument("--launches", type=int, default=8)
    ap.add_argument("--out", default=str(ROOT / "bench" / "testdata"))
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench.core import scopes, steps, trace
    from bench.core.harness import Spans, load_module, seed_key

    if jax.devices()[0].platform != "tpu":
        print("record_scopes: needs a TPU", file=sys.stderr)
        return 3
    ref = load_module(ROOT / "bench" / "configs" / "simplex-m2_reference.py")
    state = ref.initial_state(seed_key(0), args.n, "bits")
    compiled = steps.engine_step("ca").lower(state).compile()
    hlo = compiled.as_text()
    state = compiled(compiled(state))
    state.block_until_ready()

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    spans = Spans()
    tmp = ROOT / ".bench_cache" / "record_scopes"
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(str(tmp), profiler_options=options)
    with spans("window"):
        for _ in range(args.launches):
            state = compiled(state)
        state.block_until_ready()
    jax.profiler.stop_trace()
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    recorded = sorted(tmp.glob("plugins/profile/*/*.xplane.pb"))[-1]
    # source locations name the checkout's path: blank it at the same
    # length, so that the trace's length-prefixed strings stay valid
    here = f"{ROOT}/".encode()
    blank = b"_" * (len(here) - 1) + b"/"
    (out / "engine_ca_scopes_window.xplane.pb").write_bytes(
        recorded.read_bytes().replace(here, blank))
    shutil.rmtree(tmp, ignore_errors=True)
    (out / "engine_ca_scopes_step.hlo.txt").write_bytes(hlo.encode().replace(here, blank))

    summary = trace.reduce_trace(out / "engine_ca_scopes_window.xplane.pb")
    by_scope = scopes.scope_self_ns(summary, scopes.op_scopes(hlo))
    print(scopes.shares_line(by_scope))
    print(f"breakdown: {summary.breakdown()['device_ops']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
