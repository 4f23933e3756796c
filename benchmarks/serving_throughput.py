"""Serving-engine throughput benchmark: dense vs. NSVD params, dense-slab
vs. paged KV cache, and target vs. target+speculative decoding.

Drives the batched, sync-free ``ServingEngine`` on a synthetic request
workload and reports tokens/sec, decode step-time percentiles (with the
device-wait vs host-bookkeeping breakdown per step), and cache HBM bytes
for the same small LM served seven ways:

    {dense params, NSVD-compressed params} x {dense-slab cache, paged cache}
    + {NSVD target + higher-ratio NSVD draft, speculative, paged}
    + {NSVD paged, NSVD paged + speculative} with the depth-2 step pipeline
      (in-flight token futures; tok/s delta vs the depth-1 rows above)

The params axis is the paper's deployment claim (Eq. 6: an NSVD model
decodes at the cost of one rank-k ASVD); the cache axis is the engine's
memory path; the speculative row is the compression sweep's free lunch —
the same checkpoint at a higher ratio drafts k tokens per step and the
target verifies them in one chunk call (acceptance rate reported per row).

Besides the human-readable table, APPENDS a run entry to
``BENCH_serving.json`` at the repo root: each entry is stamped with the git
SHA, a hash of the benchmark config, the serving MESH shape (dp, tp,
devices) and per-device cache bytes — so the cross-PR serving perf
trajectory stays machine-readable and HBM-truthful once pools shard over a
mesh (history is never clobbered; schema-1 single entries and schema-2
mesh-less entries are auto-migrated on first touch).

    PYTHONPATH=src:. python -m benchmarks.serving_throughput [--dp N --tp M]

Sharded runs on CPU need XLA_FLAGS=--xla_force_host_platform_device_count
>= dp*tp, or the mesh falls back to (1, 1) with a warning.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import subprocess
import time
from typing import Dict, List, Optional

import numpy as np

from .common import get_grams, save_table, train_small_lm

BENCH_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_serving.json")
BENCH_SCHEMA = 8

_UNSHARDED_MESH = {"dp": 1, "tp": 1, "devices": 1}


def _migrate_entry(entry: Dict) -> Dict:
    """Schema 2 -> 3: pre-mesh entries ran single-device, so stamp the
    (1, 1) mesh and per-device bytes == global bytes (the identity the
    sharded engine reduces to on one device).  Schema 3 -> 4: pre-pipeline
    entries ran the serial dispatch->sync loop, i.e. pipeline_depth 1, with
    no device-wait/host breakdown recorded (stamped null).  Schema 4 -> 5:
    pre-auditor entries carry no static contract stamp (``audit: null``);
    fresh entries record the auditor's verdict on the roots the run used.
    Schema 5 -> 6: pre-observability entries carry no host-side telemetry
    block (TTFT/TPOT percentiles, occupancy, spec win/loss per (k,
    acceptance)) and no per-run serving-kernel roofline stamp — both
    ``null``; fresh entries record them from the repro.obs layer and
    ``benchmarks.roofline.serving_kernel_rows_for_cfg``.  Schema 6 -> 7:
    pre-scheduler rows ran the worst-case admission contract and never
    preempted — stamp ``admission_policy="worst_case"``,
    ``preempt_count=0`` and null occupancy (live/reserved was not
    measured); fresh rows record all three from
    ``engine.scheduler_stats()``.  Schema 7 -> 8: pre-fault-tolerance
    entries carry no fault accounting — ``faults: null``; fresh entries
    roll up ``engine.fault_stats()`` (injected/quarantined/retried/shed,
    all zero on a healthy bench run — the stamp proves the fault surface
    was live and silent, not absent)."""
    if "mesh" not in entry:
        entry = dict(entry, mesh=dict(_UNSHARDED_MESH))
        entry["rows"] = [
            dict(r, per_device_cache_bytes=r.get("cache_hbm_bytes"))
            if "per_device_cache_bytes" not in r else r
            for r in entry.get("rows", [])
        ]
    entry["rows"] = [
        dict({"pipeline_depth": 1, "step_device_wait_ms": None,
              "step_host_ms": None}, **r)
        for r in entry.get("rows", [])
    ]
    entry["rows"] = [
        dict({"admission_policy": "worst_case", "occupancy_live_frac": None,
              "preempt_count": 0, "mean_live_rows": None}, **r)
        for r in entry.get("rows", [])
    ]
    if "audit" not in entry:
        entry = dict(entry, audit=None)
    if "telemetry" not in entry:
        entry = dict(entry, telemetry=None)
    if "roofline" not in entry:
        entry = dict(entry, roofline=None)
    if "faults" not in entry:
        entry = dict(entry, faults=None)
    return entry


def _git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short=12", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def _config_hash(meta: Dict) -> str:
    blob = json.dumps(meta, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:12]


def append_history(entry: Dict, path: str = BENCH_PATH) -> Dict:
    """Append a stamped run entry to the bench file's history (creating or
    migrating it as needed) and return the written document."""
    history: List[Dict] = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                prev = json.load(f)
            if isinstance(prev.get("history"), list):
                history = prev["history"]
            elif prev.get("rows"):  # schema 1: one clobbered entry
                history = [prev]
        except (json.JSONDecodeError, OSError):
            history = []
    history = [_migrate_entry(e) for e in history]
    history.append(entry)
    doc = {
        "schema": BENCH_SCHEMA,
        "generated_by": "benchmarks/serving_throughput.py",
        "history": history,
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    return doc


def _make_prompts(n: int, vocab: int, seed: int) -> List[np.ndarray]:
    rng = np.random.default_rng(seed)
    return [rng.integers(2, vocab // 2, size=int(rng.integers(4, 14)))
            for _ in range(n)]


def drive(model, params, prompts, label: str, max_batch: int, max_len: int,
          max_new: int, warmup: int = 1, paged: bool = False,
          num_blocks=None, block_size: int = 16,
          spec_config=None, parallelism=None,
          pipeline_depth: int = 1, telemetry=None,
          sched_config=None, max_new_seq=None) -> Dict[str, float]:
    from repro.serving.engine import ServingEngine

    def make_engine(tel=None):
        return ServingEngine(model, params, max_batch=max_batch,
                             max_len=max_len, paged=paged,
                             num_blocks=num_blocks, block_size=block_size,
                             spec_config=spec_config,
                             parallelism=parallelism,
                             pipeline_depth=pipeline_depth,
                             telemetry=tel,
                             sched_config=sched_config)

    # Warmup pass triggers all jit compilations (prefill + decode) so the
    # timed pass measures steady-state serving.
    for _ in range(warmup):
        eng = make_engine()
        for p in prompts[:max_batch]:
            eng.submit(p, max_new_tokens=2)
        eng.run()

    # Telemetry (when requested) observes only the timed pass — warmup
    # compilations would skew the TTFT/TPOT histograms by seconds.
    eng = make_engine(telemetry)
    for i, p in enumerate(prompts):
        eng.submit(p, max_new_tokens=max_new_seq[i % len(max_new_seq)]
                   if max_new_seq else max_new)
    t0 = time.perf_counter()
    out = eng.run()
    dt = time.perf_counter() - t0
    n_tok = sum(len(v) for v in out.values())
    s = eng.stats()
    cs = eng.cache_stats()
    row = {
        "label": label,
        "cache": cs["layout"],
        "requests": len(out),
        "tokens": n_tok,
        "tok_per_s": n_tok / dt,
        "wall_s": dt,
        "decode_steps": s.get("steps", 0),
        "step_p50_ms": s.get("step_p50_s", 0.0) * 1e3,
        "step_p90_ms": s.get("step_p90_s", 0.0) * 1e3,
        "step_p99_ms": s.get("step_p99_s", 0.0) * 1e3,
        "pipeline_depth": pipeline_depth,
        # Per-step breakdown: the D2H sync stall vs the host-side
        # emission/free bookkeeping — the two halves depth>1 overlaps
        # with the device's next step.
        "step_device_wait_ms": s.get("device_wait_mean_s", 0.0) * 1e3,
        "step_host_ms": s.get("host_mean_s", 0.0) * 1e3,
        "d2h_per_step": eng.decode_transfers / max(1, s.get("steps", 1)),
        "cache_hbm_bytes": cs["cache_hbm_bytes"],
        "per_device_cache_bytes": cs["per_device_cache_hbm_bytes"],
        "cache_tokens_capacity": cs["tokens_capacity"],
        "mesh": cs["mesh"],
    }
    # Schema-7 scheduler stamp: which admission contract the row ran,
    # how much of the reserved pool held live tokens, and whether the
    # run had to preempt (always 0 when the pool covers worst case).
    sch = eng.scheduler_stats()
    row["admission_policy"] = sch["admission_policy"]
    row["occupancy_live_frac"] = sch["occupancy_live_frac"]
    row["preempt_count"] = sch["preempt_count"]
    row["mean_live_rows"] = sch["mean_live_rows"]
    # Schema-8 fault stamp: all-zero on a healthy run, proving the fault
    # surface was live (and silent) rather than absent.
    fs = eng.fault_stats()
    row["faults"] = {"injected": fs["injected_total"],
                     "quarantined": fs["quarantined"],
                     "retried": fs["retried"],
                     "shed": fs["shed"]}
    if paged:
        row["blocks_peak"] = cs["blocks_peak"]
        row["block_size"] = cs["block_size"]
        if cs.get("blocks_peak_by_shard"):
            row["blocks_peak_by_shard"] = cs["blocks_peak_by_shard"]
    extra = ""
    if spec_config is not None:
        ss = eng.spec_stats()
        row["spec_k"] = ss["k"]
        row["acceptance_rate"] = ss["acceptance_rate"]
        row["committed_per_row_step"] = ss["committed_per_row_step"]
        row["draft_hbm_bytes"] = ss["draft_hbm_bytes"]
        extra = (f" | accept={ss['acceptance_rate']:.0%} "
                 f"commit/step={ss['committed_per_row_step']:.2f}")
    print(f"  [{label:<16}|{row['cache']:<5}] {row['requests']} req, {n_tok} tok, "
          f"{row['tok_per_s']:8.1f} tok/s | step p50={row['step_p50_ms']:.2f}ms "
          f"p90={row['step_p90_ms']:.2f}ms | cache {cs['cache_hbm_bytes']/1e6:.2f}MB"
          f"{extra}")
    return row


def run(model_name: str = "small-llama", requests: int = 24, max_new: int = 24,
        max_batch: int = 8, max_len: int = 256, ratio: float = 0.2,
        block_size: int = 16, draft_ratio: float = 0.6, spec_k: int = 4,
        dp: int = 1, tp: int = 1):
    from repro.core import CompressionConfig, build_plan, compress_params
    from repro.models.api import build_draft_params
    from repro.serving.spec import SpecConfig

    parallelism = None
    mesh_meta = dict(_UNSHARDED_MESH)
    if dp * tp > 1:
        from repro.launch.mesh import make_serving_mesh
        from repro.parallel.sharding import make_parallelism

        mesh = make_serving_mesh(dp, tp)
        parallelism = make_parallelism(mesh)
        mesh_meta = {"dp": int(mesh.shape["data"]),
                     "tp": int(mesh.shape["model"]),
                     "devices": int(mesh.size)}
        print(f"  serving mesh: dp={mesh_meta['dp']} tp={mesh_meta['tp']} "
              f"({mesh_meta['devices']} device(s))")

    model, params, _ = train_small_lm(model_name)
    prompts = _make_prompts(requests, model.cfg.vocab_size, seed=0)

    # Size the paged pool from the workload: worst-case live tokens are
    # max_batch concurrent requests * (longest prompt + max_new) tokens —
    # NOT max_batch * max_len, which is the dense slab's invariant cost.
    per_req = -(-(max(len(p) for p in prompts) + max_new) // block_size)
    num_blocks = max_batch * per_req

    grams = get_grams(model, params)
    plan = build_plan(
        model.compressible_targets(),
        CompressionConfig(method="nsvd1", ratio=ratio, dtype="float32",
                          use_randomized=False),
    )
    cparams = compress_params(params, plan, grams)
    nsvd = f"nsvd-{ratio:.0%}"

    # Host-side telemetry rides the paged NSVD drive and the speculative
    # drive — the two rows the schema-6 telemetry block (TTFT/TPOT
    # percentiles, occupancy, spec win/loss per (k, acceptance)) reports.
    from repro.obs import Telemetry

    tel_paged = Telemetry()
    tel_spec = Telemetry(spec_meta={"k": spec_k, "draft_ratio": draft_ratio})

    rows = []
    for label, p in (("dense", params), (nsvd, cparams)):
        rows.append(drive(model, p, prompts, label, max_batch, max_len,
                          max_new, paged=False, parallelism=parallelism))
        rows.append(drive(model, p, prompts, label, max_batch, max_len,
                          max_new, paged=True, num_blocks=num_blocks,
                          block_size=block_size, parallelism=parallelism,
                          telemetry=tel_paged if label == nsvd else None))

    # target vs target+spec: the NSVD target verifies proposals from its
    # own higher-ratio twin (same Grams, one extra training-free pass).
    draft_params = build_draft_params(model, params, grams, draft_ratio)
    rows.append(drive(
        model, cparams, prompts, f"{nsvd}+spec", max_batch, max_len, max_new,
        paged=True, num_blocks=num_blocks, block_size=block_size,
        spec_config=SpecConfig(draft_params=draft_params, k=spec_k,
                               draft_ratio=draft_ratio),
        parallelism=parallelism, telemetry=tel_spec,
    ))

    # Pipelined vs depth-1 rows: same NSVD + paged workload with the
    # depth-2 in-flight step ring (and its speculative twin) — the
    # dispatch-ahead overlap is the tok/s delta against the depth-1 rows
    # above, with the device-wait/host breakdown showing where it came
    # from.
    rows.append(drive(model, cparams, prompts, f"{nsvd}+pipe2", max_batch,
                      max_len, max_new, paged=True, num_blocks=num_blocks,
                      block_size=block_size, parallelism=parallelism,
                      pipeline_depth=2))
    rows.append(drive(
        model, cparams, prompts, f"{nsvd}+spec+pipe2", max_batch, max_len,
        max_new, paged=True, num_blocks=num_blocks, block_size=block_size,
        spec_config=SpecConfig(draft_params=draft_params, k=spec_k,
                               draft_ratio=draft_ratio),
        parallelism=parallelism, pipeline_depth=2,
    ))

    # Overcommit rows: a mixed long/short workload against a pool HALF
    # the batch's worst-case demand (demand 2x pool).  The worst_case
    # baseline can only admit rows whose full prompt+max_new reservation
    # fits, so the batch runs part-empty; on-demand admission packs the
    # batch on prompt-sized footprints, grows per decode step, and
    # preempts the fattest row when the pool runs dry — higher mean live
    # rows, higher live/reserved occupancy, higher tok/s at the SAME
    # pool size.  Budgets alternate long/short (real traffic is not
    # uniformly worst-case — exactly the pessimism on-demand reclaims).
    from repro.serving.scheduler import SchedulerConfig

    short_new = max(2, max_new // 3)
    over_budgets = [max_new, short_new]
    longest = max(len(p) for p in prompts)
    long_b = -(-(longest + max_new) // block_size)
    short_b = -(-(longest + short_new) // block_size)
    demand_blocks = (max_batch // 2) * (long_b + short_b) \
        + (max_batch % 2) * long_b
    over_blocks = demand_blocks // 2
    over_wc = drive(model, cparams, prompts, f"{nsvd}+over-wc", max_batch,
                    max_len, max_new, paged=True, num_blocks=over_blocks,
                    block_size=block_size, parallelism=parallelism,
                    pipeline_depth=2, max_new_seq=over_budgets,
                    sched_config=SchedulerConfig(admission="worst_case",
                                                 preempt=False))
    over_od = drive(model, cparams, prompts, f"{nsvd}+over-od", max_batch,
                    max_len, max_new, paged=True, num_blocks=over_blocks,
                    block_size=block_size, parallelism=parallelism,
                    pipeline_depth=2, max_new_seq=over_budgets,
                    sched_config=SchedulerConfig(admission="on_demand",
                                                 preempt=True))
    rows.extend([over_wc, over_od])

    meta = {"model": model_name, "ratio": ratio, "draft_ratio": draft_ratio,
            "spec_k": spec_k, "max_batch": max_batch, "max_len": max_len,
            "max_new": max_new, "requests": requests,
            "block_size": block_size, "num_blocks": num_blocks,
            "dp": mesh_meta["dp"], "tp": mesh_meta["tp"]}
    save_table("serving_throughput", rows, meta)

    by = {(r["label"], r["cache"]): r for r in rows}
    dense_b = by[("dense", "dense")]["cache_hbm_bytes"]
    paged_b = by[("dense", "paged")]["cache_hbm_bytes"]
    spec_row = by[(f"{nsvd}+spec", "paged")]
    pipe_row = by[(f"{nsvd}+pipe2", "paged")]
    spec_pipe_row = by[(f"{nsvd}+spec+pipe2", "paged")]
    entry = {
        "git_sha": _git_sha(),
        "config_hash": _config_hash(meta),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "mesh": mesh_meta,
        "meta": meta,
        "rows": rows,
        "packed_kernel": _packed_kernel_stamp(model, block_size),
        "audit": _audit_stamp(model, max_batch, max_len, block_size),
        "telemetry": _telemetry_block(tel_paged, tel_spec),
        "roofline": _roofline_stamp(model, max_batch, max_len, block_size),
        "faults": {k: sum(r["faults"][k] for r in rows)
                   for k in ("injected", "quarantined", "retried", "shed")},
        "summary": {
            "per_device_cache_bytes_paged":
                by[(nsvd, "paged")]["per_device_cache_bytes"],
            "tok_per_s_dense_slab": by[(nsvd, "dense")]["tok_per_s"],
            "tok_per_s_paged": by[(nsvd, "paged")]["tok_per_s"],
            "tok_per_s_spec": spec_row["tok_per_s"],
            "tok_per_s_pipelined": pipe_row["tok_per_s"],
            "tok_per_s_spec_pipelined": spec_pipe_row["tok_per_s"],
            # Plain decode's host share is a few % of a CPU step, so its
            # overlap gain sits inside run noise off-TPU; the spec step's
            # heavier bookkeeping (multi-token commits, rollback
            # accounting) shows the pipeline's effect clearly everywhere.
            "pipeline_speedup":
                pipe_row["tok_per_s"] / max(1e-9,
                                            by[(nsvd, "paged")]["tok_per_s"]),
            "pipeline_speedup_spec":
                spec_pipe_row["tok_per_s"] / max(1e-9,
                                                 spec_row["tok_per_s"]),
            "spec_acceptance_rate": spec_row["acceptance_rate"],
            "spec_committed_per_row_step": spec_row["committed_per_row_step"],
            "cache_bytes_dense_slab": dense_b,
            "cache_bytes_paged": paged_b,
            "cache_bytes_ratio": dense_b / max(1, paged_b),
            # The scheduler's headline: same pool, same workload, the
            # admission policy alone decides how full the batch runs.
            "overcommit": {
                "pool_blocks": over_blocks,
                "demand_blocks": demand_blocks,
                "budgets": over_budgets,
                "tok_per_s_worst_case": over_wc["tok_per_s"],
                "tok_per_s_on_demand": over_od["tok_per_s"],
                "mean_live_rows_worst_case": over_wc["mean_live_rows"],
                "mean_live_rows_on_demand": over_od["mean_live_rows"],
                "occupancy_live_frac_worst_case":
                    over_wc["occupancy_live_frac"],
                "occupancy_live_frac_on_demand":
                    over_od["occupancy_live_frac"],
                "preempt_count_on_demand": over_od["preempt_count"],
            },
        },
    }
    doc = append_history(entry)
    oc = entry["summary"]["overcommit"]
    print(f"  overcommit (pool {over_blocks} blocks, worst-case demand "
          f"{demand_blocks}): worst_case {oc['tok_per_s_worst_case']:.1f} "
          f"tok/s @ {oc['mean_live_rows_worst_case']:.1f} live rows vs "
          f"on_demand {oc['tok_per_s_on_demand']:.1f} tok/s @ "
          f"{oc['mean_live_rows_on_demand']:.1f} "
          f"({oc['preempt_count_on_demand']} preempts)")
    print(f"  cache HBM: dense-slab {dense_b/1e6:.2f}MB vs paged "
          f"{paged_b/1e6:.2f}MB ({entry['summary']['cache_bytes_ratio']:.1f}x) "
          f"| spec accept={spec_row['acceptance_rate']:.0%} "
          f"| pipe2 {entry['summary']['pipeline_speedup']:.2f}x "
          f"(spec {entry['summary']['pipeline_speedup_spec']:.2f}x) "
          f"-> BENCH_serving.json [{entry['git_sha']} "
          f"{entry['config_hash']}, {len(doc['history'])} run(s)]")
    return rows


def _telemetry_block(tel_paged, tel_spec) -> Optional[Dict]:
    """Schema-6 telemetry block: host-side latency/occupancy percentiles
    from the paged NSVD drive plus the speculative drive's win/loss
    histogram keyed by (k, acceptance) — the scheduler-facing signal the
    dynamic-k controller (ROADMAP item 5) will consume."""
    try:
        block = tel_paged.bench_block()
        block["spec"] = tel_spec.bench_block()["spec"]
        return block
    except Exception as e:  # telemetry must never sink a bench run
        print(f"  telemetry block skipped: {e}")
        return None


def _roofline_stamp(model, max_batch: int, max_len: int,
                    block_size: int) -> Optional[Dict]:
    """Schema-6 serving-kernels roofline stamp: the static per-kernel
    VMEM/cost table (``benchmarks.roofline.serving_kernel_rows_for_cfg``)
    evaluated at THIS run's geometry, so every bench entry carries the
    compute/memory-bound verdict next to its measured tok/s."""
    try:
        from .roofline import serving_kernel_rows_for_cfg

        return {"serving_kernels": serving_kernel_rows_for_cfg(
            model.cfg, arch=model.cfg.name, max_batch=max_batch,
            max_len=max_len, block_size=block_size)}
    except Exception as e:  # the stamp must never sink a bench run
        print(f"  roofline stamp skipped: {e}")
        return None


def _audit_stamp(model, max_batch: int, max_len: int,
                 block_size: int) -> Optional[Dict]:
    """Schema-5 static contract stamp: the auditor's verdict on the serving
    roots this run drove — declared D2H transfers per steady step, whether
    every donated buffer aliases in the lowering, and per-kernel VMEM bytes
    per grid step.  Lowering-only (no compile), so it adds seconds, not
    minutes; any failure degrades to null rather than sinking the bench."""
    try:
        from repro.analysis.donation import audit_donation
        from repro.analysis.pallas_lint import serving_kernel_lints
        from repro.analysis.roots import audit_roots
        from repro.analysis.transfers import audit_transfers
        from repro.models.api import param_specs

        avals = param_specs(model.cfg)
        arts = audit_roots(model, avals, spec=False, compile=False,
                           max_batch=max_batch, max_len=max_len,
                           block_size=block_size)
        steady = [a for a in arts if a.spec.kind == "steady"]
        return {
            "d2h_per_step": max(
                len(audit_transfers(a).d2h_outputs) for a in steady),
            "donation_ok": all(audit_donation(a).ok for a in arts),
            "vmem_bytes_per_kernel": {
                lint.kernel: lint.vmem_bytes
                for lint in serving_kernel_lints(
                    model.cfg, max_batch=max_batch, max_len=max_len,
                    block_size=block_size)
            },
        }
    except Exception as e:  # the stamp must never sink a bench run
        print(f"  audit stamp skipped: {e}")
        return None


def _packed_kernel_stamp(model, block_size: int) -> Dict:
    """Packed-kernel entry for the bench file: the row-packed Pallas
    schedule's config for this model's decode shape plus its interpret-mode
    parity error against the per-row jnp oracle (the honest CPU-side
    evidence — MXU fill only materializes on TPU)."""
    import jax.numpy as jnp

    from repro.kernels.paged_attention.ops import (
        default_rows_per_pack,
        paged_attention,
        paged_attention_ref,
    )

    cfg = model.cfg
    hkv, g = cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads
    b, hd, m = 8, cfg.head_dim, 3
    n = b * m  # pool worst case: every row fully paged
    rpp = default_rows_per_pack(b, g)
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((b, cfg.num_heads, hd)) * 0.3,
                    jnp.float32)
    kp = jnp.asarray(
        rng.standard_normal((n, block_size, hkv, hd)) * 0.3, jnp.float32)
    vp = jnp.asarray(
        rng.standard_normal((n, block_size, hkv, hd)) * 0.3, jnp.float32)
    bt = np.full((b, m), -1, np.int32)
    lens = rng.integers(1, m * block_size + 1, size=b).astype(np.int32)
    free = iter(rng.permutation(n))
    for r, ln in enumerate(lens):
        for j in range(-(-int(ln) // block_size)):
            bt[r, j] = next(free)
    got = paged_attention(q, kp, vp, jnp.asarray(bt), jnp.asarray(lens),
                          interpret=True, rows_per_pack=rpp)
    want = paged_attention_ref(q, kp, vp, jnp.asarray(bt), jnp.asarray(lens))
    err = float(np.abs(np.asarray(got) - np.asarray(want)).max())
    return {
        "rows_per_pack": rpp,
        "gqa_group": g,
        "score_tile": [rpp * g, rpp * block_size],
        "double_buffered_dma": True,
        "max_abs_err_vs_oracle": err,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="small-llama")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--ratio", type=float, default=0.2)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--draft-ratio", type=float, default=0.6,
                    help="compression ratio of the self-speculative draft")
    ap.add_argument("--spec-k", type=int, default=4)
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel mesh axis (slots + KV pools)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel mesh axis (weights)")
    args = ap.parse_args()
    run(args.model, args.requests, args.max_new, args.max_batch,
        args.max_len, args.ratio, args.block_size, args.draft_ratio,
        args.spec_k, args.dp, args.tp)


if __name__ == "__main__":
    main()
