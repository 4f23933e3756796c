#!/usr/bin/env python3
"""Read the numbers that decide ``correct`` over many seeds, with the
control beside them, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,... \\
        [--control 3] [--seconds 51]

Each seed is one whole run of the cell (``harness.cell_run.run``: weights
from the seed, warm-up, lead-in, the measured window, the reference over
the served sample).  The first ``--control`` seeds run in control mode:
the int8 reference's picks at the same served positions go through the
harness's own verdict in the served tokens' place, and the run must come
out not correct; the served tokens' own reading comes from the same run.
One JSON line per seed, then a summary line.  The limits in the
configuration file are set from these readings.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)

    from harness import cell_run, spec
    from run import require_chips

    cell = spec.load_cell(args.workload)
    devices = require_chips(cell.chips)
    seeds = [int(s) for s in args.seeds.split(",")]
    program, control, control_correct = [], [], []
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        is_control = i < args.control
        res = cell_run.run(cell, seed, args.seconds, False, t0,
                           devices[: cell.chips], control=is_control)
        res["seed"] = seed
        res["run_s"] = time.perf_counter() - t0
        print(json.dumps(res), flush=True)
        gap = res["checks"]["served_gap_max"]["value"]
        if is_control:
            control.append(gap)
            control_correct.append(res["correct"])
            program.append(res["notes"].get("served_gap_max_of_program"))
        else:
            program.append(gap)
    print(json.dumps({"summary": args.workload, "seeds": seeds,
                      "served_gap_max": program,
                      "control_gap_max": control,
                      "control_correct": control_correct,
                      "lower": max(program), "upper": min(control)
                      if control else None}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
