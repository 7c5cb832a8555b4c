"""Run one cell of the chip benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic and its metrics are read from
``BENCHMARK.json`` at the root of the checkout. The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` and, in a traced run, ``breakdown``, followed by the
compared numbers beside their limits (``checks``), which also end standard
error. Without a TPU, with fewer chips than the cell needs, or with Pallas
forced into interpret mode, the exit code is 3 and no result is printed.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    """Parse the arguments and run the cell."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench.core.harness import process_start_time, run_cell

    return run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                    process_start_time())


if __name__ == "__main__":
    sys.exit(main())
