"""End-to-end serving driver (the paper's deployment scenario): serve a
small model with batched requests through the continuous-batching engine,
on dense, NSVD-compressed, and NSVD + self-speculative weights, and report
tokens/s + agreement.

The speculative leg is NSVD's free lunch: the SAME checkpoint compressed at
a higher ratio acts as the draft model (training-free, reusing the target's
calibration Grams), proposing k tokens per step that the target verifies in
one chunk call.  Greedy outputs are token-identical to plain decoding.

    PYTHONPATH=src:. python examples/serve_compressed.py

Multi-device serving: pass ``parallelism=`` to ``ServingEngine`` (see the
mesh leg below) — weights shard tensor-parallel, slots and KV pools
data-parallel, and outputs stay token-identical to single-device serving.
The CLI twin is ``python -m repro.launch.serve --dp 2 --tp 2``; emulate
devices on CPU with XLA_FLAGS=--xla_force_host_platform_device_count=4.
"""

import sys

sys.path.insert(0, "src")
sys.path.insert(0, ".")

import time

import numpy as np

from benchmarks.common import get_grams, train_small_lm
from repro.core import CompressionConfig, build_plan, compress_params
from repro.models.api import build_draft_params
from repro.serving.engine import ServingEngine
from repro.serving.spec import SpecConfig


def drive(model, params, prompts, label, spec_config=None, parallelism=None,
          pipeline_depth=None):
    eng = ServingEngine(model, params, max_batch=4, max_len=128,
                        spec_config=spec_config, parallelism=parallelism,
                        pipeline_depth=pipeline_depth)
    for p in prompts:
        eng.submit(p, max_new_tokens=24)
    t0 = time.time()
    out = eng.run()
    dt = time.time() - t0
    n_tok = sum(len(v) for v in out.values())
    spec = ""
    if spec_config is not None:
        ss = eng.spec_stats()
        spec = (f" | spec k={ss['k']}: accept {ss['acceptance_rate']:.0%}, "
                f"{ss['committed_per_row_step']:.2f} tok/row-step")
    print(f"  [{label}] {len(out)} requests, {n_tok} tokens "
          f"in {dt:.1f}s ({n_tok/dt:.1f} tok/s){spec}")
    return out


def main():
    model, params, _ = train_small_lm("small-llama", steps=300)
    grams = get_grams(model, params)

    cfg = CompressionConfig(method="nsvd1", ratio=0.2, dtype="float32",
                            use_randomized=False)
    plan = build_plan(model.compressible_targets(), cfg)
    cparams = compress_params(params, plan, grams)
    print(f"compressed: {plan.achieved_ratio:.1%} of params removed")

    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, 250, size=rng.integers(4, 12)) for _ in range(10)]

    dense_out = drive(model, params, prompts, "dense")
    comp_out = drive(model, cparams, prompts, "nsvd-20%")

    # Step pipelining: the engine dispatches decode step N+1 before reading
    # back step N's tokens (depth 2 is the default; depth 1 is the serial
    # loop), overlapping host bookkeeping with device compute.  Any depth
    # yields identical tokens — every finish reason exits on device.
    # CLI twin: --pipeline-depth on launch/serve.py.
    pipe1_out = drive(model, cparams, prompts, "nsvd-20% depth=1",
                      pipeline_depth=1)
    same_pipe = np.mean([pipe1_out[u] == comp_out[u] for u in comp_out])
    print(f"  depth-1 (serial) == depth-2 (pipelined) tokens: "
          f"{same_pipe:.0%} of requests")

    agree = [
        float(np.mean(np.asarray(dense_out[u][:8]) == np.asarray(comp_out[u][:8])))
        for u in dense_out
    ]
    print(f"  greedy agreement on first 8 tokens: {np.mean(agree):.0%}")

    # Self-speculative decoding: the same weights at 60% compression draft
    # for the 20% target — one extra training-free pass over the same Grams.
    # Try dynamic_k=True for per-row adaptive windows, or --spec-ratio /
    # --spec-k on launch/serve.py for the full CLI.
    draft_params = build_draft_params(model, params, grams, ratio=0.6)
    spec_out = drive(model, cparams, prompts, "nsvd-20%+spec",
                     SpecConfig(draft_params=draft_params, k=4))
    exact = np.mean([spec_out[u] == comp_out[u] for u in comp_out])
    print(f"  speculative greedy == plain greedy: {exact:.0%} of requests")

    # Mesh-sharded serving: the same engine over every available device
    # (weights TP, slots + KV pools DP).  On one device this builds a
    # (1, 1) mesh, which is bit-for-bit the meshless path; with
    # XLA_FLAGS=--xla_force_host_platform_device_count=4 it runs a real
    # (2, 2) SPMD program — and stays token-identical either way.
    import jax

    from repro.launch.mesh import make_serving_mesh
    from repro.parallel.sharding import make_parallelism

    n = jax.device_count()
    dp, tp = (2, 2) if n >= 4 else (1, 1)
    par = make_parallelism(make_serving_mesh(dp, tp))
    mesh_out = drive(model, cparams, prompts, f"nsvd-20% dp={dp} tp={tp}",
                     parallelism=par)
    same = np.mean([mesh_out[u] == comp_out[u] for u in comp_out])
    print(f"  mesh-sharded greedy == single-device greedy: {same:.0%} "
          f"of requests")

    # Telemetry: pass a repro.obs.Telemetry to the engine and the run is
    # observed from host bookkeeping alone — TTFT/TPOT percentiles, pool
    # occupancy, a Chrome-traceable event timeline — with BIT-IDENTICAL
    # tokens (jax.named_scope is metadata-only; the contract auditor
    # re-verifies one-D2H on the instrumented roots).  CLI twins:
    # --metrics-port/--metrics-json/--trace-chrome on launch/serve.py.
    from repro.obs import Telemetry

    tel = Telemetry()
    eng = ServingEngine(model, cparams, max_batch=4, max_len=128,
                        paged=True, telemetry=tel)
    uids = [eng.submit(p, max_new_tokens=24) for p in prompts]
    obs_out = eng.run()
    same_tel = np.mean([obs_out[u] == comp_out[o]
                        for u, o in zip(uids, comp_out)])
    bb = tel.bench_block()
    print(f"  telemetry leg: tokens identical to untraced run: "
          f"{same_tel:.0%} | ttft p50={bb['ttft_s']['p50']*1e3:.0f}ms "
          f"p99={bb['ttft_s']['p99']*1e3:.0f}ms | "
          f"pool peak {bb['occupancy']['pool_frac_peak']:.0%} | "
          f"{len(tel.tracer)} events captured")
    tel.tracer.export_chrome("/tmp/serve_compressed_trace.json")
    print("  chrome trace -> /tmp/serve_compressed_trace.json "
          "(load in chrome://tracing or ui.perfetto.dev)")

    # Preemption-under-pressure leg: the same requests through a block
    # pool deliberately too small for their worst case.  On-demand
    # admission reserves prompt-sized footprints and grows them per
    # decode step; when the pool runs dry the scheduler evicts the row
    # holding the most blocks (rollback + requeue) and re-prefills it
    # over prompt + generated-so-far once blocks free up.  Greedy token
    # streams survive preemption EXACTLY — the per-request PRNG chain
    # restarts deterministically on re-prefill.  CLI twins:
    # --sched-policy / --no-preempt / --num-blocks on launch/serve.py.
    from repro.serving.scheduler import SchedulerConfig

    eng = ServingEngine(model, cparams, max_batch=4, max_len=128,
                        paged=True, block_size=16, num_blocks=8,
                        sched_config=SchedulerConfig(admission="on_demand",
                                                     preempt=True))
    uids = [eng.submit(p, max_new_tokens=24) for p in prompts]
    press_out = eng.run()
    sch = eng.scheduler_stats()
    same_press = np.mean([press_out[u] == comp_out[o]
                          for u, o in zip(uids, comp_out)])
    occ = sch["occupancy_live_frac"]
    print(f"  preemption leg: pool 8 blocks (a full batch's worst case "
          f"wants 12): {sch['preempt_count']} preempts, "
          f"{sch['resumes']} resumes, {sch['grown_blocks']} grown blocks, "
          f"live/reserved {occ:.0%} | tokens identical to uncontended run: "
          f"{same_press:.0%}")

    # Fault-injection leg: serving near numerical cliffs (aggressive
    # NSVD, int8 dequant, a higher-compression draft) treats faults as a
    # first-class input.  A seeded FaultPlan poisons one request's
    # logits mid-decode and stalls one D2H sync; the device-side finite
    # check flags the poisoned row inside the existing packed D2H word,
    # the engine retries it (reprefill + capped backoff), and every
    # stream still matches the fault-free run bit-for-bit.  CLI twin:
    # --chaos PLAN.json / --max-retries / --step-timeout on
    # launch/serve.py (plus SIGTERM -> graceful drain and a /healthz
    # that answers 503 while degraded).
    from repro.serving.faults import FaultPlan, FaultPolicy, FaultSpec

    plan_f = FaultPlan([FaultSpec("poison_logits", step=3, uid=1),
                        FaultSpec("straggler", step=6, delay_s=0.05)])
    eng = ServingEngine(model, cparams, max_batch=4, max_len=128,
                        paged=True, faults=plan_f,
                        fault_policy=FaultPolicy(max_retries=2))
    uids = [eng.submit(p, max_new_tokens=24) for p in prompts]
    chaos_out = eng.run()
    fs = eng.fault_stats()
    same_chaos = np.mean([chaos_out[u] == comp_out[o]
                          for u, o in zip(uids, comp_out)])
    print(f"  chaos leg: injected {fs['injected']} -> "
          f"{fs['retried']} retried, {fs['quarantined']} quarantined | "
          f"tokens identical to fault-free run: {same_chaos:.0%} | "
          f"finish reasons all 'stop': "
          f"{all(r.finish_reason == 'stop' for r in eng.finished_requests.values())}")

    # Quality-report leg: the compression-side twin of the telemetry
    # above.  Re-compress with CompressionTelemetry attached (params stay
    # bit-identical — it only observes) and read back the per-target
    # decomposition diagnostics the quality-report CLI exports.  The full
    # pipeline — dense-vs-compressed ppl per domain, per-target logit-KL
    # attribution, append to BENCH_quality.json — is
    #   PYTHONPATH=src:. python -m repro.obs.quality_report
    # and `python -m benchmarks.sentinel` fails the build when a fresh
    # entry regresses against history at the same config.
    from repro.obs import CompressionTelemetry

    ctel = CompressionTelemetry()
    compress_params(params, plan, grams, telemetry=ctel)
    worst = max(ctel.reports.values(), key=lambda r: r.whitened_rel_err)
    print(f"  quality report: {len(ctel.reports)} targets; worst whitened "
          f"rel err {worst.whitened_rel_err:.4f} ({worst.target}, "
          f"k1/k2={worst.k1}/{worst.k2}, outlier absorption "
          f"{worst.outlier_absorption:.2f})")
    ctel.write_report("/tmp/serve_compressed_quality.json", plan=plan)
    print("  decomposition artifact -> /tmp/serve_compressed_quality.json")


if __name__ == "__main__":
    main()
