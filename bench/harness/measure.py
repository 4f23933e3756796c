"""Arithmetic the metric readers share: percentiles, the window's samples,
and the least times and useful FLOPs of the traced window."""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from . import loadgen, roofline, trace


def pct(values, q: float) -> Optional[float]:
    """The q-th percentile (numpy's linear rule), None for no samples."""
    values = np.asarray(values, float)
    return float(np.percentile(values, q)) if values.size else None


def ms(x: Optional[float]) -> Optional[float]:
    return None if x is None else x * 1e3


def in_window(run, t: float) -> bool:
    """In the measured window; in a traced run that is the traced span."""
    return run.window.start <= t < run.window.end


def dispatches(run) -> List[tuple]:
    """(t, live_rows, live_tokens) of decode dispatches in the window."""
    return [d for d in run.window.obs.dispatches if in_window(run, d[0])]


def paged_attention_least_s(run) -> Optional[float]:
    """Least time of the paged-attention calls in the trace: the work one
    chip needs for the whole batch, once per step and layer.  Each
    dispatch's FLOPs and bytes come from its live rows and cached tokens
    (mean over the traced window's dispatches), KV read from HBM, the query
    and output counted only where the call's HLO places them outside VMEM.
    Every device of a mesh runs one call per step and layer, each on its
    own shard, so the calls of one step count once: len(calls) / devices.
    Work a mesh repeats across its shards then shows as a lower share."""
    calls = run.trace.kernels.get("paged_attention", [])
    ds = dispatches(run)
    if not calls or not ds:
        return None
    cfg = run.model_cfg
    types = trace.custom_call_types(calls[0][1])
    act_in_hbm = not types[0][2]
    per = []
    for _, rows, toks in ds:
        c = roofline.paged_attention_cost(
            rows, toks + rows, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, act_bytes=2 if act_in_hbm else 0)
        per.append(roofline.least_time(c["flops"], c["bytes"],
                                       run.peaks)[0])
    return len(calls) / run.trace.devices * float(np.mean(per))


_ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4, "s8": 1, "u8": 1, "s32": 4}


def nested_lowrank_times(run) -> Optional[tuple]:
    """(least, spent) seconds of every nested low-rank kernel call in the
    trace.  Least: the larger of FLOPs over peak and bytes over HBM
    bandwidth, from the shapes in each call's HLO text.  Every factor
    streamed from HBM counts: read by the call itself (outside VMEM), or
    moved into VMEM for it by the op that produced it (its ``Feed`` read
    HBM), and then that op's time counts as the call's too.  An operand
    already in VMEM before (a buffer the program kept there) adds neither
    bytes nor time; so does the activation, which the preceding op
    computed into VMEM."""
    calls = run.trace.kernels.get("nested_lowrank_matmul", [])
    if not calls:
        return None
    least = spent = 0.0
    for dur, hlo, feeds in calls:
        (rt, rs, rv), *ops = trace.custom_call_types(hlo)
        (m, d_in), (_, k1), (_, d_out), (_, k2) = (ops[0][1], ops[1][1],
                                                   ops[2][1], ops[3][1])
        flops = roofline.nested_lowrank_cost(m, d_in, d_out, k1, k2)["flops"]
        nbytes = 0 if rv else _ITEMSIZE[rt] * int(np.prod(rs))
        spent += dur
        for i, (dt, shape, vmem) in enumerate(ops):
            size = _ITEMSIZE[dt] * int(np.prod(shape))
            feed = feeds[i] if i < len(feeds) else None
            if not vmem:
                nbytes += size
            elif i > 0 and feed is not None and feed.reads_hbm:
                nbytes += size
                spent += feed.seconds
        least += roofline.least_time(flops, nbytes, run.peaks)[0]
    return least, spent


def kernel_time_s(run, kernel: str) -> float:
    return sum(c[0] for c in run.trace.kernels.get(kernel, []))


def useful_flops(run) -> float:
    """Model FLOPs of the tokens the traced window processed: every decode
    token (linear layers, attention over its row's cache, the output head)
    and every prompt whose first token reached the harness in the traced
    window (linear layers and causal attention over its own tokens, the
    head once).  Padding rows of a prefill chunk do not count."""
    cfg = run.model_cfg
    lin = roofline.linear_flops_per_token(
        run.factored_rows, cfg.moe.top_k if cfg.moe else 0,
        run.cell.config["deployment"].get("experts_published"))
    head = roofline.head_flops(cfg.d_model, cfg.vocab_size)

    def attn(keys):
        return roofline.attention_flops(cfg.num_layers, cfg.num_heads,
                                        cfg.head_dim, keys)

    total = 0.0
    for _, rows, toks in dispatches(run):
        total += rows * (lin + head) + attn(toks + rows)
    for s in run.window.sent:
        t = loadgen.token_times(run.window, s.uid)
        if t and in_window(run, t[0][0]):
            n = len(s.req.prompt)
            total += n * lin + attn(roofline.prompt_attention_keys(n)) + head
    return total
