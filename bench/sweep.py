#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest arrival rate the engine
sustains, where neither its waiting queue nor its live rows grow through a
run and it delivers the tokens offered.

    python3 bench/sweep.py --workload <cell> --rates 0.8,1.2,1.6 \\
        [--seconds 45] [--seed 7]

On the machine with the chip, in one process: for each rate, a fresh
engine over the same weights serves the cell's traffic at that rate for
its lead-in and ``--seconds`` more, sampling the engine's waiting queue and
live rows every half second.  One JSON line per rate: the growth of each
(least-squares slope over the window), the queue at the window's end,
first tokens, tokens/s delivered beside the tokens/s offered (rate times
the mean output length), and latency percentiles.  The cell's own rate is
then fixed in its traffic file at about four fifths of the knee.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    import numpy as np

    from harness import cell_run, loadgen, spec, traffic
    from run import require_chips

    cell = spec.load_cell(args.workload)
    if cell.traffic["loop"] != "open":
        raise SystemExit("sweep: only an open-loop cell has a rate")
    devices = require_chips(cell.chips)[: cell.chips]
    import jax

    from repro.launch.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    model, params, par = cell_run.build_model_and_params(cell, args.seed,
                                                         devices)
    vocab = model.cfg.vocab_size
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(cell.traffic, rate_per_s=rate)
        eng = cell_run.build_engine(model, params, cell.config, args.seed,
                                    loadgen.make_observer(False), par)
        cell_run.warm_up(eng, vocab)
        requests = traffic.generate(mix, args.seed, args.seconds, vocab)
        drv = loadgen.LoadGen(eng, mix, requests)
        samples = []
        t0 = time.perf_counter()
        drv.run_until(t0 + mix["lead_in_s"], t0)
        w0 = time.perf_counter()
        nxt = [w0]

        def tick(now):
            if now >= nxt[0]:
                samples.append((now - w0, len(eng.queue),
                                sum(1 for r in eng.slots if r is not None)))
                nxt[0] += 0.5

        drv.run_until(w0 + args.seconds, t0, tick)
        win = loadgen.Window(w0, w0 + args.seconds, drv.sent, eng.obs,
                            [], [])
        t, q, rows = np.asarray(samples, float).T
        slope = float(np.polyfit(t, q, 1)[0]) if len(t) > 2 else 0.0
        rows_slope = float(np.polyfit(t, rows, 1)[0]) if len(t) > 2 else 0.0
        ttft = loadgen.ttft_samples(win)
        itl = loadgen.itl_samples(win)
        print(json.dumps({
            "rate_per_s": rate, "queue_slope_per_s": slope,
            "queue_end": int(q[-1]), "queue_max": int(q.max()),
            "rows_slope_per_s": rows_slope, "rows_mean": float(rows.mean()),
            "rows_max": int(rows.max()),
            "offered_tok_s": rate * float(np.mean([r.max_new
                                                   for r in requests])),
            "first_tokens": int(ttft.size),
            "out_tok_s": loadgen.tokens_in_window(win) / args.seconds,
            "ttft_p90_ms": float(np.percentile(ttft, 90)) * 1e3
            if ttft.size else None,
            "itl_p50_ms": float(np.percentile(itl, 50)) * 1e3
            if itl.size else None,
            "itl_p95_ms": float(np.percentile(itl, 95)) * 1e3
            if itl.size else None,
            "preemptions": eng.scheduler_stats()["preempt_count"],
        }), flush=True)
        eng.close()
        del eng, drv
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
