#!/usr/bin/env python3
"""Run one benchmark cell once and print one JSON result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  It builds the cell's configuration with
factored weights drawn on the device from ``--seed``, builds the program's
``ServingEngine`` with the configuration's deployment settings (on a
(dp, tp) mesh over the cell's chips where it sets one), warms up
the shapes the cell uses, offers the cell's traffic for a lead-in and then
for ``--seconds`` measured seconds, and checks the served tokens against
the plain reference.  With ``--trace 0`` the result's metrics are the
cell's end-to-end metrics; with ``--trace 1`` its per-layer metrics, read
from a profiler trace of the window's start and the engine's counters.

Without an accelerator, with fewer chips than the cell asks for, or with
a deployment whose dp x tp is not the cell's chips, it exits nonzero
before building anything or printing any result.  The last lines of
stderr, and the last key of the result line, give each number compared
with its limit.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_chips(n: int) -> dict:
    """The device JAX found, or SystemExit when it is not an accelerator
    or holds fewer than ``n`` chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise SystemExit("bench: JAX found no accelerator (platform cpu)")
    if len(devs) < n:
        raise SystemExit(f"bench: the cell needs {n} chips, JAX found "
                         f"{len(devs)}")
    return devs


def main(argv=None) -> int:
    args = parse(argv)
    from harness import spec

    if not (ROOT / "BENCHMARK.json").is_file():
        print(f"bench: no BENCHMARK.json at {ROOT}", file=sys.stderr)
        return 2
    try:
        cell = spec.load_cell(args.workload)
    except ValueError as e:     # a deployment mesh that is not its chips
        print(f"bench: {e}", file=sys.stderr)
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program at {ROOT / 'src'}", file=sys.stderr)
        return 2
    devices = require_chips(cell.chips)
    from harness import cell_run

    result = cell_run.run(cell, args.seed, args.seconds, bool(args.trace),
                          T_PROCESS, devices[: cell.chips])
    cell_run.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
