import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The lines above MUST run before any other import (jax locks the platform
and device count at first init).  The tool is compile-only and pinned to
the CPU platform, so it never takes an attached accelerator.  512
host-platform devices back both the single-pod (16 x 16 = 256) and
multi-pod (2 x 16 x 16 = 512) production meshes.

Per cell this driver:
  1. builds the model against the production mesh,
  2. jit-lowers the right step (train_step / prefill_step / decode_step)
     with full in/out shardings (ShapeDtypeStruct inputs — no allocation),
  3. compiles, printing memory_analysis() and cost_analysis(),
  4. parses the partitioned HLO for collective bytes (roofline input),
  5. writes a JSON record under experiments/dryrun/.

Usage:
  python -m repro.launch.dryrun --arch deepseek-67b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all            # every cell, both meshes
  python -m repro.launch.dryrun --all --subprocess  # isolate cells
"""

import argparse
import json
import sys
import time
from typing import Any, Dict, Optional

import jax

from repro.configs import SHAPE_CASES, applicable_shapes, get_config
from repro.configs.registry import ASSIGNED
from repro.launch.hlo_stats import collective_stats
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (
    StepConfig,
    batch_pspecs,
    cache_pspecs,
    eval_shape_opt_state,
    logits_pspec,
    make_decode_step,
    make_prefill_step,
    make_train_step,
    named,
    sanitize_pspecs,
    train_shardings,
)
from repro.models.api import build_model, input_specs
from repro.optim import AdamWConfig
from repro.parallel.sharding import make_parallelism, param_pspecs

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "experiments", "dryrun")


def _mem_dict(mem) -> Dict[str, float]:
    return {
        k: float(getattr(mem, k))
        for k in (
            "argument_size_in_bytes",
            "output_size_in_bytes",
            "temp_size_in_bytes",
            "alias_size_in_bytes",
            "generated_code_size_in_bytes",
        )
    }


def dryrun_cell(
    arch: str,
    shape: str,
    multi_pod: bool = False,
    unroll: bool = False,
    compressed_ratio: Optional[float] = None,
    chunked_loss: int = 1024,
    fsdp: bool = True,
    seq_parallel: bool = False,
    kv_quant: bool = False,
    verbose: bool = True,
) -> Dict[str, Any]:
    cfg = get_config(arch)
    case = SHAPE_CASES[shape]
    mesh = make_production_mesh(multi_pod=multi_pod)
    par = make_parallelism(mesh)
    n_chips = mesh.size

    model = build_model(cfg, par, remat=(case.kind == "train"), unroll=unroll,
                        seq_parallel=seq_parallel)
    params_shape = jax.eval_shape(model.init, jax.random.key(0))

    if compressed_ratio is not None:
        from repro.launch.compress_shapes import compressed_param_shapes

        params_shape = compressed_param_shapes(model, params_shape, compressed_ratio)

    batch = input_specs(cfg, case)
    t0 = time.time()
    fsdp_axes = par.dp_axes if (fsdp and case.kind == "train") else None
    p_pspecs = param_pspecs(params_shape, fsdp_axes=fsdp_axes)

    with jax.set_mesh(mesh):
        if case.kind == "train":
            step = make_train_step(
                model,
                AdamWConfig(),
                StepConfig(chunked_loss=chunked_loss if not cfg.is_encdec else 0),
            )
            opt_shape = eval_shape_opt_state(params_shape)
            (pi, oi, bi), (po, oo, mo) = train_shardings(
                params_shape, par, batch, fsdp=fsdp
            )
            pi = sanitize_pspecs(pi, params_shape, mesh)
            po = pi
            oi = jax.tree.map(
                lambda s_, l: sanitize_pspecs(s_, l, mesh) if hasattr(l, "shape") else s_,
                oi, opt_shape,
                is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
            )
            oo = oi
            bi = sanitize_pspecs(bi, batch, mesh)
            jitted = jax.jit(
                step,
                in_shardings=(named(pi, mesh), named(oi, mesh), named(bi, mesh)),
                out_shardings=(named(po, mesh), named(oo, mesh), named(mo, mesh)),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(params_shape, opt_shape, batch)
        elif case.kind == "prefill":
            step = make_prefill_step(model, max_len=case.seq_len)
            cache_shape = jax.eval_shape(
                lambda: model.init_cache(case.global_batch, case.seq_len)
            )
            c_specs = cache_pspecs(cache_shape, par)
            b_specs = sanitize_pspecs(batch_pspecs(batch, par), batch, mesh)
            p_in = sanitize_pspecs(p_pspecs, params_shape, mesh)
            lspec = logits_pspec(case.global_batch, cfg.vocab_size, par)
            jitted = jax.jit(
                step,
                in_shardings=(named(p_in, mesh), named(b_specs, mesh)),
                out_shardings=(
                    named(lspec, mesh),
                    named(c_specs, mesh),
                ),
            )
            lowered = jitted.lower(params_shape, batch)
        else:  # decode
            step = make_decode_step(model)
            cache_shape = jax.eval_shape(
                lambda: model.init_cache(case.global_batch, case.seq_len,
                                         kv_quant=kv_quant)
            )
            c_specs = cache_pspecs(cache_shape, par)
            b_specs = sanitize_pspecs(batch_pspecs(batch, par), batch, mesh)
            p_in = sanitize_pspecs(p_pspecs, params_shape, mesh)
            lspec = logits_pspec(case.global_batch, cfg.vocab_size, par)
            jitted = jax.jit(
                step,
                in_shardings=(
                    named(p_in, mesh),
                    named(c_specs, mesh),
                    named(b_specs, mesh),
                ),
                out_shardings=(
                    named(lspec, mesh),
                    named(c_specs, mesh),
                ),
                donate_argnums=(1,),
            )
            lowered = jitted.lower(params_shape, cache_shape, batch)

        lower_s = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        compile_s = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = collective_stats(hlo)

    result = {
        "arch": arch,
        "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "kind": case.kind,
        "unroll": unroll,
        "fsdp": bool(fsdp_axes),
        "compressed_ratio": compressed_ratio,
        "lower_s": round(lower_s, 2),
        "compile_s": round(compile_s, 2),
        "memory": _mem_dict(mem),
        "flops_per_device": float(cost.get("flops", -1.0)),
        "bytes_per_device": float(cost.get("bytes accessed", -1.0)),
        "collectives": coll,
    }
    if verbose:
        print(f"[{arch} | {shape} | {result['mesh']}] "
              f"lower={lower_s:.1f}s compile={compile_s:.1f}s")
        print(f"  memory_analysis: {mem}")
        print(f"  cost_analysis: flops={result['flops_per_device']:.3e} "
              f"bytes={result['bytes_per_device']:.3e}")
        print(f"  collectives: {json.dumps(coll, indent=None)}")
    return result


def save_result(result: Dict[str, Any], suffix: str = "") -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    name = f"{result['arch']}_{result['shape']}_{result['mesh']}{suffix}.json"
    path = os.path.join(RESULTS_DIR, name)
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    return path


def iter_cells(multi_pod_too: bool = True):
    for arch in ASSIGNED:
        cfg = get_config(arch)
        for shape in applicable_shapes(cfg):
            yield arch, shape, False
            if multi_pod_too:
                yield arch, shape, True


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--subprocess", action="store_true",
                    help="run each cell in a fresh process (isolation)")
    ap.add_argument("--unroll", action="store_true",
                    help="unroll layer scans (roofline-exact flop accounting)")
    ap.add_argument("--ratio", type=float, default=None,
                    help="NSVD compression ratio for compressed-model dry-run")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--kv-quant", action="store_true")
    args = ap.parse_args(argv)

    if args.all:
        failures = []
        for arch, shape, mp in iter_cells():
            if args.subprocess:
                import subprocess

                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape]
                if mp:
                    cmd.append("--multi-pod")
                if args.unroll:
                    cmd.append("--unroll")
                rc = subprocess.run(cmd).returncode
                if rc != 0:
                    failures.append((arch, shape, mp))
            else:
                try:
                    r = dryrun_cell(arch, shape, mp, unroll=args.unroll,
                                    compressed_ratio=args.ratio,
                                    fsdp=not args.no_fsdp)
                    save_result(r)
                except Exception as e:  # noqa: BLE001
                    print(f"FAILED {arch} {shape} mp={mp}: {e}")
                    failures.append((arch, shape, mp))
        if failures:
            print("FAILURES:", failures)
            sys.exit(1)
        print("all cells compiled OK")
        return

    r = dryrun_cell(
        args.arch, args.shape, args.multi_pod, unroll=args.unroll,
        compressed_ratio=args.ratio, fsdp=not args.no_fsdp,
        seq_parallel=args.seq_parallel, kv_quant=args.kv_quant,
    )
    suffix = "_unroll" if args.unroll else ""
    if args.ratio is not None:
        suffix += f"_r{int(args.ratio * 100)}"
    if args.seq_parallel:
        suffix += "_sp"
    if args.kv_quant:
        suffix += "_kvq"
    path = save_result(r, suffix)
    print("saved:", path)


if __name__ == "__main__":
    main()
