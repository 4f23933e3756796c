#!/usr/bin/env python3
"""Compile every configuration's serving roots for a described TPU v5e.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [config ...]

No chip is needed: the TPU compiler is installed and compiles for a
*described* v5e:2x2 topology, on one of its devices.  For each
configuration under ``bench/configs`` (or those named) it builds the
program's model from the file, draws nothing (shapes only) and compiles the
two roots a cell drives, ``paged_prefill_chunk`` and ``paged_decode``, at the
configuration's deployment geometry.  The kernels are routed as on the
chip (the backend query the program makes at trace time answers "tpu"), so
a Pallas kernel that Mosaic refuses fails here.

It prints, per configuration: head counts and vocabulary as built, the
route of every factored shape at the decode and chunk row counts, the
kernel routes each root traced, and each compiled root's memory analysis
(or the compiler's refusal) against the chip's HBM with the params and the
KV pool beside it.  It exits nonzero when a root does not compile or does
not fit.
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path
from unittest import mock

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def rehearse(name: str, one_chip, hbm_bytes: float) -> dict:
    import jax
    import jax.numpy as jnp

    from harness import spec, weights
    from repro.kernels.nested_lowrank.ops import takes_kernel
    from repro.kernels.routes import count_routes
    from repro.launch.steps import RootContext, serving_root_registry
    from repro.models import build_model

    config = spec.load_json("configs", name)
    dep = config["deployment"]
    model = build_model(spec.model_config(config))
    cfg = model.cfg
    ctx = RootContext(model=model, max_batch=dep["max_batch"],
                      max_len=dep["max_len"], block_size=dep["block_size"],
                      num_blocks=dep["num_blocks"])
    params = weights.param_shapes(model, config["compression"])
    p_bytes = weights.param_bytes(params)
    pool_bytes = weights.param_bytes(ctx.pool_avals())
    out = {"config": name, "kv_heads": cfg.num_kv_heads,
           "heads": cfg.num_heads, "vocab": cfg.vocab_size,
           "layers": cfg.num_layers, "param_bytes": p_bytes,
           "pool_bytes": pool_bytes, "routes_by_shape": [], "roots": {}}

    def place(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        for path, d_in, d_out, k1, k2, *_ in weights.factored_rows(
                model, config["compression"]):
            for rows in (dep["max_batch"],
                         dep["max_batch"] * ctx.prefill_chunk):
                out["routes_by_shape"].append({
                    "target": "/".join(path), "rows": rows, "in": d_in,
                    "out": d_out, "k1": k1, "k2": k2,
                    "pallas": bool(takes_kernel(
                        (rows, d_in), jnp.dtype(cfg.dtype), (d_in, k1),
                        (k1, d_out), (d_in, k2)))})
        for root in serving_root_registry("paged"):
            args = place(root.abstract_inputs(ctx, params))
            t0 = time.perf_counter()
            with count_routes() as tally:
                lowered = jax.jit(root.build(ctx),
                                  donate_argnums=root.donate).lower(*args)
            try:
                compiled = lowered.compile()
            except Exception as e:  # what the chip's compiler refuses
                out["roots"][root.name] = {
                    "error": str(e).strip().split("\n")[0][:300]}
                continue
            ma = compiled.memory_analysis()
            text = compiled.as_text()
            out["roots"][root.name] = {
                "compile_s": time.perf_counter() - t0,
                "routes": {f"{k}:{p}": n for (k, p), n in tally.items()},
                "tpu_custom_calls": text.count("tpu_custom_call"),
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
            }
    temps = [r["temp_bytes"] for r in out["roots"].values()
             if "temp_bytes" in r]
    worst = max(temps, default=0)
    out["fits"] = {"params+pool+worst_temp": p_bytes + pool_bytes + worst,
                   "hbm_bytes": hbm_bytes,
                   "ok": (len(temps) == len(out["roots"])
                          and p_bytes + pool_bytes + worst < hbm_bytes)}
    return out


def main(argv) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from harness.roofline import device_peaks

    # A compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep the cache off.
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = topo.devices[0]
    hbm = device_peaks(dev.device_kind)["hbm_bytes"]
    names = argv or sorted(p.stem for p in (BENCH / "configs").glob("*.json"))
    ok = True
    for name in names:
        rep = rehearse(name, SingleDeviceSharding(dev), hbm)
        print(json.dumps(rep, indent=1), flush=True)
        ok &= rep["fits"]["ok"]
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
