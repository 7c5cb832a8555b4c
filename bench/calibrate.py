"""Readings that set a cell's correctness limits: the program's and the control's.

    python3 bench/calibrate.py --workload <cell> --seeds 12 --control-seeds 3 \
        --seconds 4 --first-seed 1000

In one process, for each seed, the harness runs the cell as a benchmark run
does (set-up, a window at the cell's own load, release, check), with a
short window; the largest of the program's readings is the lower end of a
limit. For the first ``--control-seeds`` seeds it runs the cell again with
the driver's control mode on: the check then scores the control's output
(the reference one precision step down) in the program's place, on the
same requests, and must come out ``correct: false``; the smallest of its
readings is the upper end. Before each run a line ``{"calibrate": side,
"seed": n}`` names it; the run's own result line follows. The benchmark's
runs never run this. It needs the chip, as a run does.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    """Run the program's and the control's checks, one result line each."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--first-seed", type=int, default=1000)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench.core.harness import run_cell

    for k in range(args.seeds):
        seed = args.first_seed + k
        for side in ("program", "control")[: 2 if k < args.control_seeds else 1]:
            print(json.dumps({"calibrate": side, "seed": seed}), flush=True)
            rc = run_cell(args.workload, seed, args.seconds, False, time.time(),
                          overrides={"driver": {"control": side == "control"}})
            if rc:
                return rc
    return 0


if __name__ == "__main__":
    sys.exit(main())
