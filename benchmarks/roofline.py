"""Roofline analysis: three terms per (arch x shape) from the dry-run.

    compute term    = HLO_FLOPs_per_device / peak_FLOP/s          (197 TF bf16)
    memory term     = HLO_bytes_per_device / HBM_bw               (819 GB/s)
    collective term = wire_bytes_per_device / link_bw             (50 GB/s)

Sources: compiled.cost_analysis() (flops / bytes accessed, per partitioned
module = per device) and the HLO collective parse (launch/hlo_stats.py,
trip-count scaled).  cost_analysis counts a while body ONCE, so roofline
cells are lowered with --unroll (layer scans unrolled); remaining *inner*
sequence scans (chunked attention, mamba chunk scan, rwkv time scan,
chunked loss) get analytic corrections computed here — each correction is
the closed-form matmul flops of the loop body times (trip_count - 1).

MODEL_FLOPS uses the assignment's definition: 6*N*D for training (N =
active params, D = tokens) and 2*N*D for inference, plus the quadratic
attention term where applicable.  The MODEL_FLOPS / HLO_FLOPs ratio
surfaces remat and dispatch overheads.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional

from repro.configs import SHAPE_CASES, applicable_shapes, get_config
from repro.configs.registry import ASSIGNED
from repro.launch.mesh import device_peaks
from repro.models.api import count_active_params
from repro.models.blocks import resolve_specs

DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments", "dryrun")

# The dry-run compiles for a v5e mesh, so this static model prices its
# cells against that chip's published peaks (never the host it runs on).
TARGET_KIND = "TPU v5 lite"
_PEAKS = device_peaks(TARGET_KIND)
PEAK_FLOPS_BF16 = _PEAKS["flops_bf16"]
HBM_BW = _PEAKS["hbm_bw"]
ICI_BW = _PEAKS["ici_bw"]


# ------------------------------------------------------- analytic flops

def attention_flops(cfg, b, s, causal=True) -> float:
    """Score + PV matmul flops for one full forward (global)."""
    layers = sum(1 for m, _ in resolve_specs(cfg) if m in ("gqa", "mla"))
    hd = cfg.head_dim
    if cfg.attention == "mla":
        hd = cfg.mla.qk_nope_head_dim + cfg.mla.qk_rope_head_dim
    f = 4.0 * b * cfg.num_heads * hd * s * s
    if causal:
        f *= 0.5
    return f * layers


def decode_attention_flops(cfg, b, t_cache) -> float:
    layers = sum(1 for m, _ in resolve_specs(cfg) if m in ("gqa", "mla"))
    if cfg.attention == "mla":
        m = cfg.mla
        # absorbed: q_eff fold + latent scores + latent PV + unfold
        per_tok = 2 * cfg.num_heads * (
            m.qk_nope_head_dim * m.kv_lora_rank * 2  # fold q, unfold out
            + t_cache * (m.kv_lora_rank + m.qk_rope_head_dim)  # scores
            + t_cache * m.kv_lora_rank  # PV
        )
    else:
        per_tok = 4 * cfg.num_heads * cfg.head_dim * t_cache
    return float(per_tok) * b * layers


def model_flops(arch: str, shape: str) -> float:
    """Assignment formula: 6*N_active*D (train) / 2*N_active*D (infer),
    plus attention quadratic terms (global, all chips)."""
    cfg = get_config(arch)
    case = SHAPE_CASES[shape]
    n_act = count_active_params(cfg)
    if case.kind == "train":
        tokens = case.global_batch * case.seq_len
        return 6.0 * n_act * tokens + 3.0 * attention_flops(cfg, case.global_batch, case.seq_len)
    if case.kind == "prefill":
        tokens = case.global_batch * case.seq_len
        return 2.0 * n_act * tokens + attention_flops(cfg, case.global_batch, case.seq_len)
    # decode: one token per row
    return 2.0 * n_act * case.global_batch + decode_attention_flops(
        cfg, case.global_batch, case.seq_len
    )


def seq_scan_correction(arch: str, shape: str, chunked_loss: int = 1024) -> float:
    """Analytic flops invisible to cost_analysis (inner seq scans), global.

    Each term: closed-form flops of one loop body x (trips - 1); train
    cells multiply matmul terms by 3 (fwd + bwd ~ 2x), matching the 6ND
    convention.
    """
    cfg = get_config(arch)
    case = SHAPE_CASES[shape]
    b, s = case.global_batch, case.seq_len
    corr = 0.0
    bwd = 3.0 if case.kind == "train" else 1.0

    if case.kind in ("train", "prefill"):
        # chunked attention (S >= 8192): outer lax.map x inner scan -> HLO
        # sees ~1/(nq*nk) of the true quadratic work.
        if s >= 8192 and cfg.attention in ("gqa",) and any(
            m == "gqa" for m, _ in resolve_specs(cfg)
        ):
            full = attention_flops(cfg, b, s) * (1.0 if case.kind == "prefill" else 3.0)
            nq = nk = s // 1024
            corr += full * (1.0 - 1.0 / (nq * nk))
        # mamba chunk scan: ~8 flops per (token, Di, N) element.
        if cfg.mamba is not None:
            n_mamba = sum(1 for m, _ in resolve_specs(cfg) if m == "mamba")
            per = 8.0 * b * s * cfg.mamba.d_inner * cfg.mamba.d_state * n_mamba
            nchunks = max(1, s // 256)
            corr += bwd * per * (1.0 - 1.0 / nchunks)
        # rwkv time scan: ~4 flops per (token, D, hd).
        if cfg.rwkv is not None:
            n_rwkv = sum(1 for m, _ in resolve_specs(cfg) if m == "rwkv")
            per = 4.0 * b * s * cfg.d_model * cfg.rwkv.head_dim * n_rwkv
            corr += bwd * per * (1.0 - 1.0 / s)
        # chunked loss (train decoder-only): logits matmul in seq chunks.
        if case.kind == "train" and not cfg.is_encdec:
            full = 2.0 * b * s * cfg.d_model * cfg.vocab_size
            nchunks = max(1, s // chunked_loss)
            corr += 3.0 * full * (1.0 - 1.0 / nchunks)
    return corr


# ----------------------------------------------------------- table build

def load_cell(arch: str, shape: str, mesh: str = "16x16", prefer_unroll=True) -> Optional[Dict]:
    for suffix in (["_unroll", ""] if prefer_unroll else [""]):
        p = os.path.join(DRYRUN_DIR, f"{arch}_{shape}_{mesh}{suffix}.json")
        if os.path.exists(p):
            with open(p) as f:
                return json.load(f)
    return None


def roofline_row(arch: str, shape: str, mesh: str = "16x16") -> Optional[Dict]:
    cell = load_cell(arch, shape, mesh)
    if cell is None:
        return None
    chips = cell["n_chips"]
    hlo_flops_dev = cell["flops_per_device"]
    corr_dev = seq_scan_correction(arch, shape) / chips
    flops_dev = hlo_flops_dev + corr_dev
    flops_source = "hlo+corr" if cell.get("unroll") else "analytic"
    if not cell.get("unroll"):
        # Scan-mode HLO counts each layer-scan body once — flops are a
        # known undercount.  Fall back to the analytic model count with a
        # remat overhead factor (6ND -> 8ND) for train cells; the HLO
        # value is kept as a lower bound in `hlo_flops_dev`.
        kind = SHAPE_CASES[shape].kind
        overhead = 4.0 / 3.0 if kind == "train" else 1.0
        flops_dev = max(flops_dev, model_flops(arch, shape) * overhead / chips)
    bytes_dev = cell["bytes_per_device"]
    # Scan-mode HLO bytes share the undercount; floor at one read of every
    # argument + one write of the outputs (weights/cache must stream at
    # least once per step).
    floor = cell["memory"]["argument_size_in_bytes"] + cell["memory"]["output_size_in_bytes"]
    bytes_dev = max(bytes_dev, floor)
    wire_dev = cell["collectives"]["total"]["wire_bytes"]

    compute_t = flops_dev / PEAK_FLOPS_BF16
    memory_t = bytes_dev / HBM_BW
    coll_t = wire_dev / ICI_BW
    terms = {"compute": compute_t, "memory": memory_t, "collective": coll_t}
    dominant = max(terms, key=terms.get)
    mf = model_flops(arch, shape)
    step_time = max(terms.values())
    useful_ratio = (mf / chips) / flops_dev if flops_dev > 0 else 0.0
    # Roofline fraction: useful model flops per device over what the chip
    # could do in the bound step time.
    roofline_frac = (mf / chips / step_time) / PEAK_FLOPS_BF16 if step_time > 0 else 0.0
    return {
        "arch": arch,
        "shape": shape,
        "mesh": mesh,
        "unroll": cell.get("unroll", False),
        "flops_source": flops_source,
        "compute_s": compute_t,
        "memory_s": memory_t,
        "collective_s": coll_t,
        "dominant": dominant,
        "model_flops": mf,
        "hlo_flops_dev": hlo_flops_dev,
        "scan_corr_dev": corr_dev,
        "useful_ratio": useful_ratio,
        "roofline_frac": roofline_frac,
        "mem_args_gb": cell["memory"]["argument_size_in_bytes"] / 2**30,
        "mem_temp_gb": cell["memory"]["temp_size_in_bytes"] / 2**30,
    }


# ------------------------------------------- serving-kernel static stamp

def serving_kernel_rows_for_cfg(cfg, *, arch: Optional[str] = None,
                                max_batch: int = 64, max_len: int = 4096,
                                block_size: int = 16,
                                kv_quant: bool = False) -> List[Dict]:
    """Static per-kernel roofline stamp for the serving path: VMEM bytes
    per grid step (from repro.analysis.pallas_lint, the same inventory the
    contract auditor checks) plus the packed paged-attention cost model at
    the full context length — FLOPs, HBM bytes, arithmetic intensity, and
    the MXU junk-work factor of row packing.  No dry-run artifact needed:
    everything is a closed-form function of the config geometry, so any
    cfg works — registry archs AND the bench's ad-hoc small LMs (this is
    the core ``benchmarks/serving_throughput.py`` stamps per run)."""
    from repro.analysis.pallas_lint import (
        paged_attention_cost,
        serving_kernel_lints,
    )

    rows: List[Dict] = []
    for lint in serving_kernel_lints(cfg, max_batch=max_batch,
                                     max_len=max_len, block_size=block_size,
                                     kv_quant=kv_quant):
        row = {
            "arch": arch or getattr(cfg, "name", "custom"),
            "kernel": lint.kernel,
            "vmem_bytes": lint.vmem_bytes,
            "vmem_frac": lint.vmem_bytes / lint.vmem_limit,
            "fits": lint.fits,
            "misaligned_tiles": len(lint.misaligned),
        }
        if lint.kernel == "paged_attention":
            cost = paged_attention_cost(
                max_batch, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                block_size, max_len, quant=kv_quant)
            mxu_t = cost["flops_mxu"] / PEAK_FLOPS_BF16
            hbm_t = cost["hbm_bytes"] / HBM_BW
            row.update(
                rows_per_pack=cost["rows_per_pack"],
                flops_useful=cost["flops_useful"],
                flops_mxu=cost["flops_mxu"],
                hbm_bytes=cost["hbm_bytes"],
                intensity=cost["intensity"],
                pack_overhead=cost["flops_mxu"] / max(1, cost["flops_useful"]),
                bound="compute" if mxu_t > hbm_t else "memory",
            )
        rows.append(row)
    return rows


def serving_kernel_rows(arch: str, *, max_batch: int = 64,
                        max_len: int = 4096, block_size: int = 16,
                        kv_quant: bool = False) -> List[Dict]:
    """Registry-arch wrapper over :func:`serving_kernel_rows_for_cfg`."""
    return serving_kernel_rows_for_cfg(
        get_config(arch), arch=arch, max_batch=max_batch, max_len=max_len,
        block_size=block_size, kv_quant=kv_quant)


def build_table(mesh: str = "16x16") -> List[Dict]:
    rows = []
    for arch in ASSIGNED:
        for shape in applicable_shapes(get_config(arch)):
            r = roofline_row(arch, shape, mesh)
            if r is not None:
                rows.append(r)
    return rows


def main():
    t0 = time.time()
    rows = build_table()
    print(f"{'arch':<24}{'shape':<13}{'comp(s)':>10}{'mem(s)':>10}{'coll(s)':>10}"
          f"{'bound':>12}{'useful':>8}{'roofl%':>8}")
    for r in rows:
        print(f"{r['arch']:<24}{r['shape']:<13}{r['compute_s']:>10.4f}"
              f"{r['memory_s']:>10.4f}{r['collective_s']:>10.4f}"
              f"{r['dominant']:>12}{r['useful_ratio']:>8.2f}"
              f"{100*r['roofline_frac']:>7.1f}%")
    serving = []
    for arch in ASSIGNED:
        try:
            serving.extend(serving_kernel_rows(arch))
        except Exception as e:  # configs without a serving path
            print(f"serving-kernel stamp skipped for {arch}: {e}")
    if serving:
        print(f"\n{'arch':<24}{'kernel':<18}{'vmem':>9}{'pack':>6}"
              f"{'intensity':>11}{'bound':>9}")
        for r in serving:
            extra = (f"{r['rows_per_pack']:>6}{r['intensity']:>11.1f}"
                     f"{r['bound']:>9}"
                     if r["kernel"] == "paged_attention" else "")
            print(f"{r['arch']:<24}{r['kernel']:<18}"
                  f"{r['vmem_bytes']/2**20:>8.2f}M{extra}")
    out = os.path.join(os.path.dirname(__file__), "..", "experiments", "roofline.json")
    with open(out, "w") as f:
        json.dump({"cells": rows, "serving_kernels": serving}, f, indent=1)
    avg_frac = sum(r["roofline_frac"] for r in rows) / max(len(rows), 1)
    print(f"roofline,{(time.time()-t0)*1e6:.0f},{avg_frac:.4f}")
    return rows


if __name__ == "__main__":
    main()
