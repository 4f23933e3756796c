"""One run of one cell: set-up, lead-in, window, reduction, correctness.

``run`` is everything ``bench/run.py`` does after it has found the chips,
so that a test can drive a whole run at a small size on the CPU.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import shutil
import sys
import tempfile
import time
from typing import Callable, Optional

import numpy as np
from repro.models import build_model

from . import check, loadgen, spec, traffic, weights


@dataclasses.dataclass
class Run:
    """What a metric reader reads (``bench/metrics/<name>.py``)."""
    cell: spec.Cell
    model_cfg: object
    factored_rows: list
    window: loadgen.Window
    setup_s: float
    peaks: Optional[dict] = None       # chip peaks, traced runs
    trace: Optional[object] = None     # harness.trace.Reduction


class CompileClock:
    """Backend compiles, from JAX's monitoring events, with their end time."""

    def __init__(self):
        import jax

        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.perf_counter(), secs))

    def count(self, t0: float, t1: float) -> int:
        return sum(1 for t, _ in self.events if t0 <= t < t1)


def engine_seed(seed: int) -> int:
    return int(np.random.SeedSequence([int(seed), 4]).generate_state(1)[0]
               >> 1)


def parallelism(cell: spec.Cell, devices):
    """The program's Parallelism over the cell's (dp, tp) serving mesh of
    ``devices``; None on one chip, where the engine runs meshless."""
    dp, tp = cell.mesh()
    if dp * tp == 1:
        return None
    from repro.launch.mesh import make_serving_mesh
    from repro.parallel.sharding import make_parallelism

    mesh = make_serving_mesh(dp, tp)
    if set(mesh.devices.flat) != set(devices):
        raise ValueError(f"the (dp {dp}, tp {tp}) mesh is not the cell's "
                         f"{len(devices)} devices")
    return make_parallelism(mesh)


def build_model_and_params(cell: spec.Cell, seed: int, devices):
    """(model, params, par): the model the cell's configuration describes,
    its factored params drawn from ``seed``, and the Parallelism of its
    mesh (None on one chip).  On a mesh each param is drawn straight into
    the sharding the engine gives it (``ServingShardings.params``)."""
    par = parallelism(cell, devices)
    config = cell.config
    model = build_model(spec.model_config(config))
    shardings = None
    if par is not None:
        from repro.launch.steps import ServingShardings

        shapes = weights.param_shapes(model, config["compression"])
        shardings = ServingShardings(par, shapes, None,
                                     config["deployment"]["max_batch"]).params
    params = weights.build_params(model, config["compression"], seed,
                                  shardings)
    return model, params, par


def build_engine(model, params, config: dict, seed: int, observer,
                 par=None):
    from repro.serving.engine import ServingEngine

    dep = config["deployment"]
    return ServingEngine(model, params, max_batch=dep["max_batch"],
                         max_len=dep["max_len"], seed=engine_seed(seed),
                         paged=True, block_size=dep["block_size"],
                         num_blocks=dep["num_blocks"], parallelism=par,
                         telemetry=observer)


def warm_up(eng, vocab: int) -> None:
    """Compile and run every program the window will run: the chunked
    prefill root (a two-chunk prompt), the decode root, and the per-request
    key derivation the engine runs eagerly at every count of rows that can
    finish a prompt in one chunk tick (it compiles once per count)."""
    rng = np.random.default_rng(0)
    n = min(2, eng.max_batch)
    for _ in range(n):
        uid = eng.submit(rng.integers(0, vocab, eng.prefill_chunk + 6,
                                      dtype=np.int32), max_new_tokens=4)
        eng.obs.expect[uid] = 4
    eng.run()
    eng.drain()
    for rows in range(1, eng.max_batch + 1):
        eng._request_keys(list(range(rows)))


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool,
        t_process: float, devices, fault: Optional[Callable] = None,
        control: bool = False) -> dict:
    """One run.  ``fault(engine)`` breaks the timed path before warm-up (a
    test's planted fault).  With ``control`` the control takes the timed
    path's place in the comparison: the int8 reference's own picks at the
    served positions are scored and judged as served tokens are, so the
    run has to come out not correct; the served tokens' own reading joins
    the notes."""
    import jax

    from repro.launch.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    clock = CompileClock()
    dev = devices[0]
    config = cell.config
    marks = [("start", t_process), ("imports", time.perf_counter())]
    model, params, par = build_model_and_params(cell, seed, devices)
    jax.block_until_ready(params)
    marks.append(("weights", time.perf_counter()))
    observer = loadgen.make_observer(traced)
    eng = build_engine(model, params, config, seed, observer, par)
    if fault is not None:
        fault(eng)
    vocab = model.cfg.vocab_size
    from repro.kernels.routes import count_routes

    with count_routes() as tally:
        warm_up(eng, vocab)
    print(json.dumps({"kernel_routes_per_trace": {
        f"{k}:{p}": n for (k, p), n in sorted(tally.items())}}),
        file=sys.stderr, flush=True)
    marks.append(("engine_and_warm_up", time.perf_counter()))
    requests = traffic.generate(cell.traffic, seed, seconds, vocab)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    stamp = {}
    win = loadgen.drive(eng, cell.traffic, requests, seconds, traced,
                       trace_dir, lambda t: stamp.setdefault("w", t))
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peak = max(peaks) if None not in peaks else None
    setup_s = stamp["w"] - t_process
    in_window = clock.count(win.start, win.end)

    attempted = [s for s in win.sent if win.start <= s.sent < win.end]
    reasons = {s.uid: getattr(eng.finished_requests.get(s.uid),
                              "finish_reason", None) for s in attempted}
    failed = sum(1 for r in reasons.values() if r not in (None, "stop"))
    sched = eng.scheduler_stats()
    done = [(s.req.prompt, list(eng.finished_requests[s.uid].generated))
            for s in loadgen.finished(win)
            if s.uid in eng.finished_requests]
    r = Run(cell, model.cfg,
            weights.factored_rows(model, config["compression"]), win,
            setup_s)
    if traced:
        from . import roofline
        from . import trace as trace_mod

        r.peaks = roofline.device_peaks(dev.device_kind)
        r.trace = trace_mod.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = spec.read_metrics(cell.per_layer if traced else cell.end_to_end,
                                r)
    # The program's state goes before the reference runs.
    eng.close()
    del eng, observer
    gc.collect()
    limits = config["correct"]
    sample = check.draw_sample(done, seed, limits["served_tokens_min"])
    extra = {}
    t_ref = time.perf_counter()
    gaps = check.served_gaps(params, config, sample) if sample else \
        np.zeros(0)
    if control and sample:
        extra["served_gap_max_of_program"] = float(gaps.max())
        gaps = check.control_gaps(params, config, sample)
    t_ref = time.perf_counter() - t_ref
    correct, checks = check.verdict(gaps, failed, len(attempted), limits)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": len(attempted),
           "failed": failed, "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = r.trace.busy_s
        device["window_s"] = r.trace.window_s
        out["breakdown"] = r.trace.breakdown()
        roots = {}
        for name, ds in r.trace.modules.items():
            key = name.split("(")[0]
            roots[key] = roots.get(key, 0.0) + sum(ds)
        extra["device_s_by_program"] = roots
    marks.append(("lead_in", stamp["w"]))
    out["notes"] = {
        "setup_s_parts": {b[0]: b[1] - a[1] for a, b in zip(marks, marks[1:])},
        "compile_s_in_setup": sum(s for t, s in clock.events
                                  if t < stamp["w"]),
        "reference_s": t_ref,
        "control": control,
        "compiles_in_window": in_window,
        "memory_peak_bytes_by_device": peaks,
        "preemptions": sched["preempt_count"],
        "requests_finished": len(done),
        "compared_requests": len(sample),
        "first_tokens_in_window": int(loadgen.ttft_samples(win).size),
        **extra,
    }
    out["checks"] = checks
    return out


def report(result: dict) -> None:
    """Numbers compared as the last stderr lines, the result as the last
    stdout line (its last key the same numbers with their limits)."""
    print(json.dumps({"notes": result["notes"]}), file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
