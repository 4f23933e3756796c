"""Shared harness for the paper-reproduction benchmarks.

Trains small from-scratch LMs (no pretrained weights exist offline —
DESIGN.md §10), caches them under experiments/models/, collects calibration
Grams on the en_a domain (the WikiText-2 stand-in), and exposes
compress+eval helpers used by every table script.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional, Tuple

import jax

from repro.calib.runner import calibration_batches, collect_grams
from repro.checkpoint.manager import CheckpointManager
from repro.configs.paper_models import LLAMA_7B, MISTRAL_7B, OPT_6_7B, small_lm
from repro.core import CompressionConfig, GramStore, compress_params, build_plan
from repro.data.pipeline import LMDataPipeline, PipelineState
from repro.eval.perplexity import eval_batches, evaluate_ppl
from repro.launch.steps import StepConfig, make_train_step
from repro.models import build_model
from repro.optim import AdamWConfig, init_state, linear_warmup_cosine

ROOT = os.path.join(os.path.dirname(__file__), "..", "experiments")
MODELS_DIR = os.path.join(ROOT, "models")
RESULTS_DIR = os.path.join(ROOT, "repro")

VOCAB = 512
SEQ = 128
EVAL_DOMAINS = ("en_a", "en_b", "task", "zh", "jp")

SMALL_CONFIGS = {
    "small-llama": dict(family_of=LLAMA_7B, num_layers=4, d_model=128, d_ff=352),
    "small-llama-13b": dict(family_of=LLAMA_7B, num_layers=6, d_model=192, d_ff=512),
    "small-opt": dict(family_of=OPT_6_7B, num_layers=4, d_model=128, d_ff=512),
    "small-mistral": dict(family_of=MISTRAL_7B, num_layers=4, d_model=128, d_ff=352),
}


def get_small_config(name: str):
    kw = SMALL_CONFIGS[name]
    return small_lm(name=name, vocab_size=VOCAB, **kw)


def train_small_lm(
    name: str,
    steps: int = 300,
    batch: int = 16,
    lr: float = 1e-3,
    force: bool = False,
    log_every: int = 50,
):
    """Train (or load cached) a small LM on the calibration domain."""
    cfg = get_small_config(name)
    model = build_model(cfg)
    ckpt_dir = os.path.join(MODELS_DIR, name)
    mgr = CheckpointManager(ckpt_dir, keep=1, async_save=False)
    if not force and mgr.latest_step() is not None:
        params, extra, _ = mgr.restore()
        return model, params, extra

    params = model.init(jax.random.key(0))
    opt_cfg = AdamWConfig(lr=lr, weight_decay=0.01,
                          schedule=linear_warmup_cosine(20, steps))
    opt = init_state(params)
    step_fn = jax.jit(make_train_step(model, opt_cfg, StepConfig()))
    pipe = LMDataPipeline(VOCAB, batch, SEQ, PipelineState(seed=0, step=0, domain="mix"))
    t0 = time.time()
    last_loss = None
    for i in range(steps):
        b = next(pipe)
        params, opt, metrics = step_fn(params, opt, b)
        if (i + 1) % log_every == 0:
            last_loss = float(metrics["loss"])
            print(f"  [{name}] step {i+1}/{steps} loss={last_loss:.3f} "
                  f"({time.time()-t0:.0f}s)")
    extra = {"steps": steps, "final_loss": last_loss}
    mgr.save(0, params, extra, block=True)
    return model, params, extra


def gram_cache_key(cfg) -> str:
    """Cache key of a model's calibration Grams: everything that shapes
    them (name, depth, widths, vocabulary)."""
    return (f"{cfg.name}-L{cfg.num_layers}-d{cfg.d_model}"
            f"-h{cfg.num_heads}x{cfg.num_kv_heads}x{cfg.head_dim}"
            f"-ff{cfg.d_ff}-v{cfg.vocab_size}")


def get_grams(model, params, n_samples: int = 256, force: bool = False,
              cache: bool = True) -> GramStore:
    """Calibration Grams of ``model`` on the en_a domain, with tokens drawn
    from the model's own vocabulary; cached on disk under the config's
    key unless ``cache`` is False."""
    cfg = model.cfg
    path = os.path.join(MODELS_DIR, cfg.name,
                        f"grams-{gram_cache_key(cfg)}.npz")
    if cache and not force and os.path.exists(path):
        return GramStore.load(path)
    store = collect_grams(
        model, params,
        calibration_batches(cfg.vocab_size, "en_a", n_samples=n_samples,
                            batch=16, seq=SEQ),
    )
    if cache:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        store.save(path)
    return store


def compress_and_eval(
    model,
    params,
    grams: GramStore,
    method: str,
    ratio: float,
    k1_frac: float = 0.90,
    domains: Tuple[str, ...] = EVAL_DOMAINS,
    eval_n_batches: int = 8,
) -> Dict[str, float]:
    """Compress with (method, ratio, k1) and return PPL per domain."""
    cfg = CompressionConfig(
        method=method, ratio=ratio, k1_frac=k1_frac, dtype="float32",
        use_randomized=False,
    )
    plan = build_plan(model.compressible_targets(), cfg)
    cparams = compress_params(params, plan, grams)
    out = {"_achieved_ratio": plan.achieved_ratio}
    for d in domains:
        out[d] = evaluate_ppl(
            model, cparams,
            eval_batches(VOCAB, d, n_batches=eval_n_batches, batch=16, seq=SEQ),
        )
    return out


def baseline_ppl(model, params, domains=EVAL_DOMAINS, eval_n_batches: int = 6):
    return {
        d: evaluate_ppl(
            model, params, eval_batches(VOCAB, d, n_batches=eval_n_batches, batch=16, seq=SEQ)
        )
        for d in domains
    }


def save_table(name: str, rows: List[Dict], meta: Optional[Dict] = None):
    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(os.path.join(RESULTS_DIR, f"{name}.json"), "w") as f:
        json.dump({"meta": meta or {}, "rows": rows}, f, indent=1)


def load_table(name: str) -> Optional[List[Dict]]:
    """Cached table rows (benchmarks recompute only when missing or
    REPRO_FORCE=1 — keeps the final `benchmarks.run` pass fast and
    deterministic)."""
    if os.environ.get("REPRO_FORCE"):
        return None
    path = os.path.join(RESULTS_DIR, f"{name}.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)["rows"]


def fmt_row(label: str, ppls: Dict[str, float]) -> str:
    cells = " ".join(f"{d}={ppls[d]:9.2f}" for d in EVAL_DOMAINS if d in ppls)
    return f"  {label:<28} {cells}"
