"""The yardstick: published chip peaks, and the operations and bytes each
kernel and each served token needs, computed from shapes.

The ops/bytes arithmetic follows ``repro.analysis.pallas_lint``'s cost
model and the peaks ``repro.launch.mesh.DEVICE_PEAKS``; both are copied
here so that the measure cannot move with the program.  Each function
counts what the algorithm needs (useful work, bytes it must move at least),
so a time derived from them is a lower bound and a share of it cannot pass
100% unless the time leaves out part of the work.
"""

from __future__ import annotations

from typing import Optional

# Published per-chip peaks, keyed by the ``device_kind`` JAX reports.
# Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
# 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip
# interconnect over four links (50 GB/s per link).
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bw": 819e9,
        "ici_bw": 50e9,
        "hbm_bytes": 16e9,
    },
}


def device_peaks(kind: str) -> dict:
    """Peaks of one chip of ``kind``; a kind not in the table is an error."""
    try:
        return DEVICE_PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known kinds: {sorted(DEVICE_PEAKS)}") from None


def least_time(flops: float, nbytes: float, peaks: dict):
    """(seconds, bound): the larger of compute and memory time."""
    t_c = flops / peaks["flops_bf16"]
    t_m = nbytes / peaks["hbm_bw"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def paged_attention_cost(live_rows: int, live_tokens: int, num_q_heads: int,
                         num_kv_heads: int, head_dim: int,
                         kv_bytes: int = 2, act_bytes: int = 2) -> dict:
    """One decode call of paged attention over ``live_rows`` rows holding
    ``live_tokens`` cached tokens between them: QK^T and PV (4 FLOPs per
    query head, head element and cached token), every cached K and V
    element read once, each live row's query read and output written."""
    flops = 4 * num_q_heads * head_dim * live_tokens
    nbytes = (2 * num_kv_heads * head_dim * kv_bytes * live_tokens
              + 2 * live_rows * num_q_heads * head_dim * act_bytes)
    return {"flops": flops, "bytes": nbytes}


def nested_lowrank_cost(rows: int, d_in: int, d_out: int, k1: int, k2: int,
                        dtype_bytes: int = 2) -> dict:
    """One nested low-rank matmul y = (x u) v + (x u2) v2 on ``rows`` rows:
    both factor pairs read once, x read and y written once."""
    k = k1 + k2
    flops = 2 * rows * (d_in * k + k * d_out)
    nbytes = dtype_bytes * (d_in * k + k * d_out + rows * (d_in + d_out))
    return {"flops": flops, "bytes": nbytes}


def linear_flops_per_token(factored_rows, top_k: int = 0,
                           experts_published: Optional[int] = None):
    """Multiply-adds (x2) of every factored linear of every layer for one
    token; ``factored_rows`` as ``weights.factored_rows`` gives them.  A
    token runs only its ``top_k`` routed experts, of the
    ``experts_published`` that a deployment spreads over its chips (by
    default the experts held here): of a stack of E held experts it runs
    top_k x E / experts_published on this chip.  Shared experts and every
    other linear run whole."""
    total = 0
    for row in factored_rows:
        n = 1
        for s in row.stacked:
            n *= s
        if row.experts:
            held = row.stacked[-1]
            n = n // held * top_k * held / (experts_published or held)
        total += 2 * n * (row.in_dim + row.out_dim) * (row.k1 + row.k2)
    return total


def attention_flops(num_layers: int, num_q_heads: int, head_dim: int,
                    keys: int) -> int:
    """QK^T and PV of one query token over ``keys`` keys, every layer."""
    return 4 * num_layers * num_q_heads * head_dim * keys


def head_flops(d_model: int, vocab: int) -> int:
    """The output head for one token that is sampled."""
    return 2 * d_model * vocab


def prompt_attention_keys(n: int) -> int:
    """Keys a causal prompt of ``n`` tokens attends over in all: sum p+1."""
    return n * (n + 1) // 2
