"""Serving launcher: load (or compress) a model and run batched requests.

Mesh-sharded serving (``--dp``/``--tp``): the engine runs its decode /
chunked-prefill / speculative roots SPMD over a (dp, tp) mesh — weights
tensor-parallel, slots + KV pools data-parallel.  A (1, 1) mesh (or no
flags) is bit-for-bit the single-device engine.  Examples:

    # 4-chip host: 2-way data x 2-way tensor parallel, paged cache
    python -m repro.launch.serve --dp 2 --tp 2 --max-batch 8

    # emulate the same on CPU
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python -m repro.launch.serve --dp 2 --tp 2 --max-batch 8

``--max-batch`` should be a multiple of ``--dp`` (otherwise per-slot state
stays replicated and only the weights shard); ``--num-blocks`` rounds up
to a multiple of ``--dp`` so every shard holds an equal sub-pool."""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, Dict, List, NamedTuple

import jax
import numpy as np

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.launch.compile_cache import enable_compilation_cache
from repro.models import build_model
from repro.serving.engine import ServingEngine


class Served(NamedTuple):
    """What one ``serve`` call leaves behind: the (closed) engine, its
    token streams by uid, the params it served, the model and its config,
    and host seconds per phase (calibrate / compress / serve; serve
    includes the first compile of every root)."""

    engine: ServingEngine
    outputs: Dict[int, List[int]]
    params: Any
    model: Any
    cfg: ModelConfig
    seconds: Dict[str, float]


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="small-llama")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the arch's smoke-size variant (--no-reduced "
                         "keeps the published widths)")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut depth to N layers, keeping every width")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=8,
                    help="tokens per seeded random prompt")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--compress", type=float, default=None,
                    help="NSVD ratio (requires calibration pass)")
    ap.add_argument("--paged", choices=("auto", "on", "off"), default="auto",
                    help="KV-cache layout (auto: paged for attention models)")
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="paged pool size (default: dense-capacity parity)")
    ap.add_argument("--prefill-chunk", type=int, default=64)
    ap.add_argument("--eos", type=int, default=None,
                    help="EOS token id (device-side early exit)")
    ap.add_argument("--spec-ratio", type=float, default=None,
                    help="enable self-speculative decoding with a draft "
                         "compressed at this (higher) NSVD ratio")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="speculation window: draft tokens per step")
    ap.add_argument("--spec-dynamic-k", action="store_true",
                    help="per-row adaptive speculation windows")
    ap.add_argument("--sched-policy", choices=("on_demand", "worst_case"),
                    default="on_demand",
                    help="paged admission policy: on_demand admits on "
                         "prompt-sized reservations and grows per decode "
                         "step at block boundaries; worst_case reserves "
                         "prompt+max_new up front (the pre-scheduler "
                         "contract)")
    ap.add_argument("--priority-classes", default=None, metavar="A,B,...",
                    help="comma-separated latency classes, highest "
                         "priority first (default: single 'default' "
                         "class, plain FIFO); requests here all land in "
                         "the lowest class")
    ap.add_argument("--no-preempt", action="store_true",
                    help="never evict a live row when the block pool runs "
                         "dry: starved rows stall (frozen on device) "
                         "until blocks free up, and a genuine full-pool "
                         "deadlock raises instead of thrashing")
    ap.add_argument("--pipeline-depth", type=int, default=None,
                    help="in-flight decode steps (default 2, or the "
                         "REPRO_SERVING_PIPELINE_DEPTH env var): the engine "
                         "dispatches step N+1 before consuming step N's "
                         "token transfer, overlapping host token/slot "
                         "bookkeeping with device compute. 1 disables the "
                         "overlap (bit-for-bit the serial engine); any "
                         "depth produces identical token streams")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel mesh axis: slots, per-slot state "
                         "and KV pools shard over dp devices (max-batch "
                         "should divide it)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel mesh axis: weights shard over tp "
                         "devices (factored NSVD layers all-reduce rank-k "
                         "partials, so TP collectives shrink with "
                         "compression). dp*tp must fit jax.device_count() "
                         "(the run refuses to start otherwise); use "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N to emulate on CPU")
    ap.add_argument("--audit", action="store_true",
                    help="preflight the static contract auditor "
                         "(repro.analysis) over this run's serving roots "
                         "before serving; refuses to start on a violation")
    ap.add_argument("--transfer-guard", action="store_true",
                    help="run the steady-state decode loop under "
                         "jax.transfer_guard('disallow'): any implicit "
                         "host<->device transfer raises instead of "
                         "silently stalling the step pipeline (also via "
                         "REPRO_SERVING_TRANSFER_GUARD=1)")
    fault_g = ap.add_argument_group(
        "fault tolerance", "deterministic chaos + degradation policy "
        "(repro.serving.faults); off by default — an engine without a "
        "plan takes no extra hot-path branches")
    fault_g.add_argument("--chaos", default=None, metavar="PLAN.json",
                         help="inject the FaultPlan in PLAN.json "
                              '({"faults": [{"kind": "straggler", '
                              '"step": 4}, ...]}): the engine must absorb '
                              "every fault without perturbing healthy "
                              "token streams; a fault report prints at "
                              "exit")
    fault_g.add_argument("--max-retries", type=int, default=0,
                         help="poisoned-request retry budget (reprefill "
                              "from committed context with capped "
                              "exponential backoff) before the request "
                              "retires with finish_reason='error'")
    fault_g.add_argument("--step-timeout", type=float, default=None,
                         metavar="SECONDS",
                         help="hard per-step wall-clock limit: exceeding "
                              "it raises a structured ServingFault with "
                              "an engine snapshot for post-mortem")
    obs_g = ap.add_argument_group(
        "observability", "host-side telemetry (repro.obs): any flag here "
        "enables the tracer + metrics registry; all are off by default "
        "and the disabled path is a pinned no-op")
    obs_g.add_argument("--metrics-port", type=int, default=None,
                       help="serve Prometheus text at :PORT/metrics, a "
                            "JSON snapshot at :PORT/metrics.json and a "
                            "liveness probe at :PORT/healthz while "
                            "running (0 picks a free port)")
    obs_g.add_argument("--metrics-json", default=None, metavar="PATH",
                       help="write a final JSON metrics snapshot here")
    obs_g.add_argument("--trace-jsonl", default=None, metavar="PATH",
                       help="export the event ring buffer as JSONL")
    obs_g.add_argument("--trace-chrome", default=None, metavar="PATH",
                       help="export the event ring buffer as a Chrome "
                            "trace (load in chrome://tracing or Perfetto)")
    obs_g.add_argument("--profile-dir", default=None, metavar="DIR",
                       help="capture a jax.profiler trace of the first "
                            "--profile-steps decode steps into DIR")
    obs_g.add_argument("--profile-steps", type=int, default=8,
                       help="steps to profile with --profile-dir")
    return ap


def model_config(args) -> ModelConfig:
    """The registry config of ``--arch``, reduced and/or depth-cut as
    asked (``--layers`` keeps every width)."""
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.layers is not None:
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
    return cfg


def load_model(args):
    """(model, params): a trained small LM, or ``model_config(args)`` with
    seeded random weights."""
    if args.arch.startswith("small-"):
        from benchmarks.common import train_small_lm

        model, params, _ = train_small_lm(args.arch)
        return model, params
    model = build_model(model_config(args))
    return model, model.init(jax.random.key(args.seed))


def serve(args) -> Served:
    """The CLI's whole path for parsed ``args``: load or build the model,
    calibrate + NSVD-compress when asked, then serve ``--requests``
    seeded prompts through one ``ServingEngine``."""
    enable_compilation_cache()
    seconds: Dict[str, float] = {}
    model, params = load_model(args)
    cfg = model.cfg

    # Observability comes up BEFORE compression so the compression pass
    # (when --compress rides along) reports into the same registry the
    # serving loop exports on --metrics-port.
    telemetry = None
    metrics_server = None
    compress_telemetry = None
    # The metrics server is built before the engine exists; /healthz reads
    # the engine's degradation surface through this late-bound reference.
    health_ref = {}
    obs_wanted = any(v is not None for v in (
        args.metrics_port, args.metrics_json, args.trace_jsonl,
        args.trace_chrome, args.profile_dir))
    if obs_wanted:
        from repro.obs import CompressionTelemetry, MetricsServer, Telemetry

        telemetry = Telemetry(profile_dir=args.profile_dir,
                              profile_steps=args.profile_steps)
        compress_telemetry = CompressionTelemetry(registry=telemetry.metrics)
        if args.metrics_port is not None:
            metrics_server = MetricsServer(
                telemetry.metrics, port=args.metrics_port,
                health=lambda: (health_ref["eng"].degraded_components()
                                if "eng" in health_ref else {}))
            print(f"metrics: {metrics_server.url} "
                  "(+ /metrics.json, /healthz)")

    base_params = params
    grams = None
    if args.compress is not None or args.spec_ratio is not None:
        from benchmarks.common import get_grams

        t0 = time.perf_counter()
        # Trained small LMs keep their Grams on disk; a seeded random-
        # weight model recalibrates (cheap next to GBs of f64 Grams).
        grams = get_grams(model, params,
                          cache=args.arch.startswith("small-"))
        seconds["calibrate"] = time.perf_counter() - t0
    if args.compress is not None:
        from repro.core import CompressionConfig, build_plan, compress_params

        t0 = time.perf_counter()
        # Factorization math runs in float64 on the host; the factors are
        # stored in the model's own dtype.  use_randomized lets best_svd
        # take the randomized SVD only where a matrix is large enough.
        plan = build_plan(
            model.compressible_targets(),
            CompressionConfig(method="nsvd1", ratio=args.compress,
                              dtype=cfg.dtype, use_randomized=True),
        )
        params = compress_params(base_params, plan, grams,
                                 telemetry=compress_telemetry)
        seconds["compress"] = time.perf_counter() - t0
        print(f"serving NSVD-compressed weights ({plan.achieved_ratio:.0%} removed)")

    spec_config = None
    if args.spec_ratio is not None:
        from repro.models.api import build_draft_params
        from repro.serving.spec import SpecConfig

        draft_params = build_draft_params(model, base_params, grams,
                                          args.spec_ratio)
        spec_config = SpecConfig(draft_params=draft_params, k=args.spec_k,
                                 dynamic_k=args.spec_dynamic_k,
                                 draft_ratio=args.spec_ratio)
        print(f"speculative decoding: nsvd-{args.spec_ratio:.0%} draft, "
              f"k={args.spec_k}"
              + (" (dynamic per-row)" if args.spec_dynamic_k else ""))

    parallelism = None
    if args.dp * args.tp > 1:
        from repro.launch.mesh import make_serving_mesh
        from repro.parallel.sharding import make_parallelism

        mesh = make_serving_mesh(args.dp, args.tp)
        parallelism = make_parallelism(mesh)
        print(f"serving mesh: dp={mesh.shape['data']} "
              f"tp={mesh.shape['model']} ({mesh.size} device(s))")

    if args.audit:
        from repro.analysis.run import audit_layout
        from repro.models.api import cache_layout, param_specs

        native = cache_layout(model)
        layout = {"auto": native, "on": "paged", "off": "dense"}[args.paged]
        rows = audit_layout(model, param_specs(cfg), layout, parallelism,
                            spec=spec_config is not None,
                            max_batch=args.max_batch, max_len=args.max_len,
                            spec_k=args.spec_k)
        bad = [r["root"] for r in rows if not r["ok"]]
        if bad:
            raise SystemExit(
                f"serving-root contract audit FAILED for {bad}; run "
                "python -m repro.analysis.run for the full report")
        print(f"audit: {len(rows)} {layout} roots clean "
              "(transfers/donation/sharding/dtypes)")

    faults = None
    fault_policy = None
    if (args.chaos is not None or args.max_retries
            or args.step_timeout is not None):
        from repro.serving.faults import FaultPlan, FaultPolicy

        if args.chaos is not None:
            faults = FaultPlan.from_json(args.chaos)
            print(f"chaos: {len(faults)} seeded fault(s) from {args.chaos}")
        fault_policy = FaultPolicy(max_retries=args.max_retries,
                                   step_timeout_s=args.step_timeout)

    from repro.serving.scheduler import SchedulerConfig

    sched_config = SchedulerConfig(
        admission=args.sched_policy,
        preempt=not args.no_preempt,
        priority_classes=tuple(
            c.strip() for c in args.priority_classes.split(",") if c.strip())
        if args.priority_classes else ("default",),
    )
    eng = ServingEngine(model, params, max_batch=args.max_batch,
                        max_len=args.max_len, seed=args.seed,
                        paged={"auto": None, "on": True, "off": False}[args.paged],
                        block_size=args.block_size,
                        num_blocks=args.num_blocks,
                        prefill_chunk=args.prefill_chunk,
                        eos_id=args.eos,
                        spec_config=spec_config,
                        parallelism=parallelism,
                        pipeline_depth=args.pipeline_depth,
                        transfer_guard=args.transfer_guard or None,
                        telemetry=telemetry,
                        sched_config=sched_config,
                        faults=faults,
                        fault_policy=fault_policy)
    health_ref["eng"] = eng
    # SIGTERM = graceful drain: stop admitting, shed the queue, let live
    # rows finish their in-flight steps, then run() returns normally.
    import signal

    signal.signal(signal.SIGTERM, lambda *_: eng.request_drain())
    rng = np.random.default_rng(args.seed)
    for _ in range(args.requests):
        eng.submit(rng.integers(2, cfg.vocab_size // 2, size=args.prompt_len),
                   max_new_tokens=args.max_new,
                   temperature=args.temperature)
    t0 = time.perf_counter()
    # The metrics server thread must come down with the engine, crash or
    # clean exit alike — a daemon thread holding the port would outlive a
    # failed run in long-lived launchers.
    try:
        try:
            out = eng.run()
        finally:
            # Idempotent engine teardown: sheds anything still queued/
            # parked and retires live rows with finish_reason='shutdown'
            # (a no-op after a clean run).  Its errors surface; on a failed
            # run they chain onto the run's own error.
            eng.close()
    except BaseException:
        if metrics_server is not None:
            metrics_server.close()
        raise
    dt = time.perf_counter() - t0
    seconds["serve"] = dt
    n = sum(len(v) for v in out.values())
    dev = jax.devices()[0]
    print(f"{len(out)} requests, {n} tokens in {dt:.2f}s host time "
          f"(includes compile) on {dev.platform}/{dev.device_kind}")
    s = eng.stats()
    if s.get("steps"):
        print(f"decode steps: {s['steps']} (pipeline depth "
              f"{s['pipeline_depth']})  "
              f"p50={s['step_p50_s']*1e3:.2f}ms  "
              f"p90={s['step_p90_s']*1e3:.2f}ms  "
              f"p99={s['step_p99_s']*1e3:.2f}ms  "
              f"[device wait {s['device_wait_mean_s']*1e3:.2f}ms + host "
              f"{s['host_mean_s']*1e3:.2f}ms per step]")
    cs = eng.cache_stats()
    extra = (f"  peak blocks={cs['blocks_peak']}/{cs['num_blocks']}"
             if cs["layout"] == "paged" else "")
    if cs.get("blocks_peak_by_shard"):
        extra += f"  per-shard peaks={cs['blocks_peak_by_shard']}"
    mesh_s = cs["mesh"]
    per_dev = (f" ({cs['per_device_cache_hbm_bytes']/1e6:.2f}MB/device, "
               f"dp={mesh_s['dp']} tp={mesh_s['tp']})"
               if mesh_s["devices"] > 1 else "")
    print(f"cache[{cs['layout']}]: {cs['cache_hbm_bytes']/1e6:.2f}MB{per_dev}, "
          f"capacity {cs['tokens_capacity']} tok{extra}")
    sch = eng.scheduler_stats()
    if cs["layout"] == "paged":
        occ = sch["occupancy_live_frac"]
        occ_s = f"{occ:.0%}" if occ is not None else "n/a"
        print(f"sched[{sch['admission_policy']}]: live/reserved {occ_s}, "
              f"{sch['preempt_count']} preempts, {sch['resumes']} resumes, "
              f"{sch['grown_blocks']} grown blocks, {sch['stalls']} stalls, "
              f"swap {sch['swap_bytes']/1e6:.2f}MB")
    ss = eng.spec_stats()
    if ss:
        print(f"spec[k={ss['k']}]: acceptance {ss['acceptance_rate']:.0%}, "
              f"{ss['committed_per_row_step']:.2f} committed tok/row-step, "
              f"draft cache {ss['draft_hbm_bytes']/1e6:.2f}MB")
    if faults is not None or fault_policy is not None:
        fs = eng.fault_stats()
        inj = ", ".join(f"{k}={v}" for k, v in sorted(fs["injected"].items()))
        print(f"faults: injected [{inj or 'none'}], "
              f"quarantined={fs['quarantined']} retried={fs['retried']} "
              f"shed={fs['shed']} swap_fallbacks={fs['swap_fallbacks']} "
              f"draft_kills={fs['draft_kills']}/"
              f"reenables={fs['draft_reenables']} "
              f"straggler slow/trips={fs['straggler_slow']}/"
              f"{fs['straggler_trips']}")
        if faults is not None and faults.outstanding():
            kinds = [sp.kind for sp in faults.outstanding()]
            print(f"faults: {len(kinds)} spec(s) never found an injection "
                  f"site: {kinds}")

    if telemetry is not None:
        if telemetry.profile is not None:
            telemetry.profile.stop()
        bb = telemetry.bench_block()
        print(f"telemetry: ttft p50={bb['ttft_s']['p50']*1e3:.1f}ms "
              f"p99={bb['ttft_s']['p99']*1e3:.1f}ms  "
              f"tpot p50={bb['tpot_s']['p50']*1e3:.2f}ms  "
              f"{len(telemetry.tracer)} events "
              f"({telemetry.tracer.dropped} dropped)")
        if args.metrics_json:
            from repro.obs import write_metrics_json

            write_metrics_json(telemetry.metrics, args.metrics_json,
                               extra={"engine": {"stats": s, "cache": cs,
                                                 "spec": ss}})
            print(f"metrics snapshot -> {args.metrics_json}")
        if args.trace_jsonl:
            telemetry.tracer.export_jsonl(args.trace_jsonl)
            print(f"event trace (jsonl) -> {args.trace_jsonl}")
        if args.trace_chrome:
            telemetry.tracer.export_chrome(args.trace_chrome)
            print(f"chrome trace -> {args.trace_chrome}")
        if args.profile_dir:
            print(f"jax.profiler trace -> {args.profile_dir}")
        if metrics_server is not None:
            metrics_server.close()
    return Served(eng, out, params, model, cfg, seconds)


def main():
    serve(build_parser().parse_args())


if __name__ == "__main__":
    main()
