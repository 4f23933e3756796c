#!/usr/bin/env python3
"""Smoke run of NSVD compress-and-serve on a TPU, at Mistral-7B widths.

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # (2, 2) DP x TP mesh vs one chip

One chip, in one process, through ``repro.launch.serve.serve`` (the CLI's
own path) on ``mistral-7b --no-reduced --layers 2``: every published width
(d_model 4096, 32 heads, 8 KV heads, head_dim 128, d_ff 14336, vocab
32000) in bf16 with seeded random weights, depth cut to 2 layers.

  1. calibration Grams from the device forward (calib/runner.collect_grams);
  2. NSVD-1 compression at ratio 0.2 (host, float64; factors in bf16);
  3. a paged ServingEngine at pipeline depth 2 serving 8 seeded 64-token
     prompts x 32 greedy tokens;
  4. the paged-attention and nested-lowrank kernels at the smoke's own
     shapes against their ``ref.py`` oracles;
  5. every served token against one teacher-forced float32 forward of the
     served params.

``--four-chips`` runs only the sharded path and what it is compared with:
the same requests on a (2, 2) mesh and on one chip, both checked against
the teacher-forced reference, plus their token agreement.  Its factored
params are drawn from a seed at the NSVD plan's ranks (no host
decomposition, which would idle four chips for minutes).

Any failure exits nonzero.  Without an accelerator, or outside a checkout
of the repository, it exits nonzero before printing any result.  The last
line of stdout is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent

ARCH, LAYERS, RATIO = "mistral-7b", 2, 0.2
REQUESTS, PROMPT_LEN, MAX_NEW, MAX_LEN = 8, 64, 32, 128
# The reference forward runs REQUESTS x REF_LEN = 2048 rows, more than
# nested_lowrank.ops.MAX_KERNEL_ROWS, so it takes the XLA matmul path and
# not the kernels under test.  Right padding cannot change causal logits.
REF_LEN = 256
# A served token may differ from the float32 reference argmax only where
# the reference's top-2 logit margin is below MARGIN_TOL.  The engine
# samples from bf16 logits: near the top (|z| in 2..8 for these N(0, 1)
# logits) one bf16 step is 2^-6..2^-5 = 0.016..0.031, and two bf16 layers
# of rounding in the hidden state move a logit by a few hundredths more.
# 0.1 is about twice that sum.
MARGIN_TOL = 0.1
# Kernel vs oracle: both kernels accumulate in float32 but round their
# output (and the nested kernel its rank-k intermediate) to bf16, 2^-8
# relative each; the MXU may take bf16 passes on float32 operands, another
# 2^-8.  Errors are held to 2% of the reference's largest magnitude,
# about five times that sum.
KERNEL_RTOL = 0.02


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


class CompileClock:
    """Host seconds of XLA backend compiles, from JAX's own monitoring
    events, each stamped with the time it ended.  (Trace and lowering
    events nest inside each other, so they are not summed.)"""

    def __init__(self, jax):
        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.perf_counter(), secs))

    def since(self, t0: float) -> float:
        return sum(s for t, s in self.events if t >= t0)


def serve_argv(*extra: str):
    from repro.launch.serve import build_parser

    return build_parser().parse_args([
        "--arch", ARCH, "--no-reduced", "--layers", str(LAYERS),
        "--requests", str(REQUESTS), "--prompt-len", str(PROMPT_LEN),
        "--max-new", str(MAX_NEW), "--max-batch", str(REQUESTS),
        "--max-len", str(MAX_LEN), "--paged", "on",
        "--pipeline-depth", "2", "--seed", "0", *extra])


def describe(cfg) -> str:
    return (f"model {cfg.name}: d_model {cfg.d_model}, heads {cfg.num_heads}, "
            f"kv_heads {cfg.num_kv_heads}, head_dim {cfg.head_dim}, "
            f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, layers "
            f"{cfg.num_layers}, dtype {cfg.dtype}")


def peak_bytes(jax) -> int:
    return int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])


def rel_err(jnp, got, ref) -> float:
    ref = ref.astype(jnp.float32)
    err = jnp.max(jnp.abs(got.astype(jnp.float32) - ref))
    return float(err / jnp.maximum(jnp.max(jnp.abs(ref)), 1e-30))


# ------------------------------------------------------------ kernel checks


def check_paged_attention(jax, jnp, cfg, num_blocks: int) -> None:
    """The Pallas paged-attention kernel at the smoke's decode shapes
    against ``paged_attention_ref`` in float32 under highest precision."""
    import numpy as np

    from repro.kernels.paged_attention.ops import (
        paged_attention,
        paged_attention_ref,
    )

    bs = 16
    m = MAX_LEN // bs
    ks = jax.random.split(jax.random.key(11), 3)
    dt = jnp.dtype(cfg.dtype)
    q = jax.random.normal(ks[0], (REQUESTS, cfg.num_heads, cfg.head_dim), dt)
    pool = (num_blocks, bs, cfg.num_kv_heads, cfg.head_dim)
    kp = jax.random.normal(ks[1], pool, dt)
    vp = jax.random.normal(ks[2], pool, dt)
    rng = np.random.default_rng(11)
    tables = rng.permutation(num_blocks)[: REQUESTS * m].reshape(REQUESTS, m)
    # Page edges, a full row, and a one-token row.
    lengths = np.array([1, 15, 16, 17, 64, 95, 96, MAX_LEN])[:REQUESTS]
    bt = jnp.asarray(tables, jnp.int32)
    ln = jnp.asarray(lengths, jnp.int32)
    out = paged_attention(q, kp, vp, bt, ln, use_kernel=True)
    with jax.default_matmul_precision("highest"):
        ref = paged_attention_ref(q.astype(jnp.float32),
                                  kp.astype(jnp.float32),
                                  vp.astype(jnp.float32), bt, ln)
    err = rel_err(jnp, out, ref)
    say(f"paged-attention kernel vs ref: rel err {err:.3e} "
        f"(tol {KERNEL_RTOL}) at q {tuple(q.shape)}, pools {pool} {dt}")
    check(err <= KERNEL_RTOL, f"paged-attention kernel off by {err:.3e}")


def factored_leaves(tree, path=()):
    """(path, leaf) of every nested-factored linear in a params tree."""
    if isinstance(tree, dict):
        if {"u", "v", "u2", "v2"} <= tree.keys():
            yield path, tree
            return
        for k, v in tree.items():
            yield from factored_leaves(v, path + (k,))


def check_nested_lowrank(jax, jnp, params) -> int:
    """The Pallas nested-lowrank kernel against its oracle for every
    factored shape the smoke's decode step routes to it (rows =
    REQUESTS); returns how many shapes were checked."""
    from repro.kernels.nested_lowrank.ops import (
        nested_lowrank_matmul,
        takes_kernel,
    )
    from repro.kernels.nested_lowrank.ref import nested_lowrank_matmul_ref

    seen = set()
    for path, leaf in factored_leaves(params):
        # Stacked (scanned) layers: check the first layer's slice.
        u, v, u2, v2 = (leaf[k].reshape(-1, *leaf[k].shape[-2:])[0]
                        for k in ("u", "v", "u2", "v2"))
        shape = (u.shape, v.shape, u2.shape)
        x_shape = (REQUESTS, u.shape[0])
        if shape in seen or not takes_kernel(x_shape, u.dtype, u.shape,
                                             v.shape, u2.shape):
            continue
        seen.add(shape)
        x = jax.random.normal(jax.random.key(len(seen)), x_shape, u.dtype)
        y = nested_lowrank_matmul(x, u, v, u2, v2, use_kernel=True)
        with jax.default_matmul_precision("highest"):
            ref = nested_lowrank_matmul_ref(
                *(a.astype(jnp.float32) for a in (x, u, v, u2, v2)))
        err = rel_err(jnp, y, ref)
        say(f"nested-lowrank kernel vs ref at {'/'.join(path)} "
            f"(x {x_shape}, k1 {u.shape[1]}, k2 {u2.shape[1]}, "
            f"n {v.shape[1]}): rel err {err:.3e} (tol {KERNEL_RTOL})")
        check(err <= KERNEL_RTOL, f"nested-lowrank kernel off by {err:.3e}")
    return len(seen)


# ------------------------------------------------- teacher-forced reference


def reference_check(jax, jnp, model, params, prompts, streams, label: str,
                    bf16_delta: bool = False) -> None:
    """Every served token against the argmax of one teacher-forced float32
    forward of ``params`` (highest precision, XLA path), allowing a
    mismatch only where the reference's top-2 margin is below
    MARGIN_TOL."""
    import dataclasses

    import numpy as np

    from repro.models import build_model

    check(len(streams) == REQUESTS, f"{label}: {len(streams)} streams")
    seqs = np.zeros((REQUESTS, REF_LEN), np.int32)
    for i, (p, g) in enumerate(zip(prompts, streams)):
        check(len(p) == PROMPT_LEN and len(g) == MAX_NEW,
              f"{label}: request {i} has prompt {len(p)}, {len(g)} tokens")
        seqs[i, : PROMPT_LEN + MAX_NEW - 1] = np.concatenate([p, g[:-1]])
    model32 = build_model(dataclasses.replace(model.cfg, dtype="float32"))
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    sl = slice(PROMPT_LEN - 1, PROMPT_LEN - 1 + MAX_NEW)

    @jax.jit
    def fwd(p, t):
        return model32.apply(p, t, mode="train")[0][:, sl]

    with jax.default_matmul_precision("highest"):
        logits = fwd(p32, jnp.asarray(seqs))
    top2, idx = jax.lax.top_k(logits, 2)
    top2, idx = np.asarray(top2), np.asarray(idx)
    margin = top2[..., 0] - top2[..., 1]
    served = np.asarray(streams)
    near = margin < MARGIN_TOL
    miss = served != idx[..., 0]
    bad = miss & ~near
    say(f"{label}: teacher-forced check: {served.size} tokens, "
        f"{int(near.sum())} near-ties (margin < {MARGIN_TOL}), "
        f"{int(miss.sum())} mismatches, all within near-ties: "
        f"{not bad.any()}")
    if bf16_delta:
        # How far the model's own bf16 forward (XLA path) sits from the
        # float32 reference on the same positions: the scale MARGIN_TOL
        # must cover.
        fwd16 = jax.jit(lambda p, t: model.apply(p, t, mode="train")[0][:, sl])
        delta = jnp.max(jnp.abs(fwd16(params, jnp.asarray(seqs))
                                .astype(jnp.float32) - logits))
        say(f"{label}: bf16 vs float32 teacher-forced logits: max |delta| "
            f"{float(delta):.4f}")
    check(not bad.any(),
          f"{label}: {int(bad.sum())} tokens differ from the reference "
          f"argmax with margin >= {MARGIN_TOL} (largest "
          f"{float(margin[bad].max()):.4f})" if bad.any() else "")


# ------------------------------------------------------------------ paths


def one_chip(jax, jnp) -> None:
    from repro.kernels.routes import count_routes
    from repro.launch.serve import model_config, serve

    args = serve_argv("--compress", str(RATIO))
    say(describe(model_config(args)))
    clock = CompileClock(jax)
    with count_routes() as tally:
        served = serve(args)
    t_end = time.perf_counter()
    routes = {k: {p: n for (kk, p), n in tally.items() if kk == k}
              for k in ("nested_lowrank", "paged_attention")}
    secs = served.seconds
    compile_s = clock.since(t_end - secs["serve"])
    say(f"host seconds: calibrate {secs['calibrate']:.1f}, compress "
        f"{secs['compress']:.1f}, serve {secs['serve']:.1f} (of which "
        f"XLA compile {compile_s:.1f}; the rest serves, traces and "
        f"lowers)")
    say(f"kernel routing (call sites per trace): {json.dumps(routes)}")
    say(f"peak_bytes_in_use after serving: {peak_bytes(jax)}")
    check(routes["paged_attention"].get("pallas", 0) > 0,
          "no paged-attention call site took the Pallas kernel")
    check(routes["paged_attention"].get("xla", 0) == 0,
          "a paged-attention call site took the XLA oracle on the chip")

    eng = served.engine
    uids = sorted(served.outputs)
    reqs = [eng.finished_requests[u] for u in uids]
    check(all(r.finish_reason == "stop" for r in reqs),
          f"finish reasons {[r.finish_reason for r in reqs]}")
    check(all(r.preemptions == 0 for r in reqs), "a request was preempted")
    prompts = [r.prompt for r in reqs]
    streams = [served.outputs[u] for u in uids]

    check_paged_attention(jax, jnp, served.cfg,
                          eng.cache_stats()["num_blocks"])
    n = check_nested_lowrank(jax, jnp, served.params)
    say(f"nested-lowrank kernel checked at {n} decode shape(s)")
    reference_check(jax, jnp, served.model, served.params, prompts, streams,
                    "one chip", bf16_delta=True)
    say(f"peak_bytes_in_use at end: {peak_bytes(jax)}")


def random_factored(jax, jnp, model, params, ratio: float, seed: int):
    """``params`` with every compression target replaced by nested factors
    at the NSVD plan's ranks, drawn from ``seed`` with the dense kernel's
    output scale (u ~ N(0, 1/in), v ~ N(0, 1/k))."""
    from repro.core import CompressionConfig, build_plan
    from repro.core.nsvd import split_rank

    plan = build_plan(model.compressible_targets(),
                      CompressionConfig(method="nsvd1", ratio=ratio,
                                        dtype=model.cfg.dtype))
    new = jax.tree.map(lambda a: a, params)  # fresh dicts, same leaves
    dt = jnp.dtype(model.cfg.dtype)
    keys = jax.random.split(jax.random.key(seed), len(plan.targets))
    for key, spec in zip(keys, plan.targets):
        rank = plan.rank_of(spec)
        k1, k2 = split_rank(rank, plan.config.k1_frac)
        lead = tuple(spec.stacked)
        ku, kv, ku2, kv2 = jax.random.split(key, 4)

        def draw(k, shape, fan_in):
            return (jax.random.normal(k, lead + shape, jnp.float32)
                    / fan_in ** 0.5).astype(dt)

        node = new
        for name in spec.path[:-1]:
            node = node[name]
        node[spec.path[-1]] = {
            "u": draw(ku, (spec.in_dim, k1), spec.in_dim),
            "v": draw(kv, (k1, spec.out_dim), rank),
            "u2": draw(ku2, (spec.in_dim, k2), spec.in_dim),
            "v2": draw(kv2, (k2, spec.out_dim), rank),
        }
    return new


def four_chips(jax, jnp) -> None:
    import numpy as np

    from repro.launch.mesh import make_serving_mesh
    from repro.launch.serve import model_config
    from repro.models import build_model
    from repro.parallel.sharding import make_parallelism
    from repro.serving.engine import ServingEngine

    check(len(jax.devices()) == 4, f"{len(jax.devices())} devices, not 4")
    cfg = model_config(serve_argv())
    say(describe(cfg))
    model = build_model(cfg)
    params = random_factored(jax, jnp, model, model.init(jax.random.key(0)),
                             RATIO, seed=1)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, cfg.vocab_size // 2, size=PROMPT_LEN)
               for _ in range(REQUESTS)]
    runs = {}
    for label, dp, tp in (("mesh (2,2)", 2, 2), ("one chip", 1, 1)):
        par = (make_parallelism(make_serving_mesh(dp, tp))
               if dp * tp > 1 else None)
        eng = ServingEngine(model, params, max_batch=REQUESTS,
                            max_len=MAX_LEN, seed=0, paged=True,
                            pipeline_depth=2, parallelism=par)
        uids = [eng.submit(p, max_new_tokens=MAX_NEW) for p in prompts]
        t0 = time.perf_counter()
        try:
            out = eng.run()
        finally:
            eng.close()
        say(f"{label}: served {len(uids)} requests in "
            f"{time.perf_counter() - t0:.1f} host s (includes compile), "
            f"mesh {eng.mesh_shape()}")
        runs[label] = [out[u] for u in uids]
        reference_check(jax, jnp, model, params, prompts, runs[label], label)
    a, b = (np.asarray(r) for r in runs.values())
    same_req = sum(bool((x == y).all()) for x, y in zip(a, b))
    say(f"mesh (2,2) vs one chip: {int((a == b).sum())}/{a.size} tokens "
        f"agree, {same_req}/{REQUESTS} requests identical")
    say(f"peak_bytes_in_use on device 0 at end: {peak_bytes(jax)}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="serve on a (2, 2) mesh and on one chip; run "
                         "nothing else")
    opts = ap.parse_args()
    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: no repository next to this script (src/repro "
              "is missing)", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    say(f"device {dev.platform} / {dev.device_kind} x {len(jax.devices())}")
    try:
        (four_chips if opts.four_chips else one_chip)(jax, jnp)
    except Exception:
        traceback.print_exc()
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
