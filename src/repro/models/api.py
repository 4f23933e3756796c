"""Model facade: build any registered arch, get inputs/steps/targets.

``build_model(cfg, par)`` returns a DecoderLM or EncDecLM; ``input_specs``
produces ShapeDtypeStruct stand-ins for every input of a shape case
(weak-type-correct, shardable, no device allocation) — the dry-run contract.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Union

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeCase
from repro.parallel.sharding import NONE_PARALLEL, Parallelism

from .encdec import EncDecLM
from .transformer import DecoderLM, VISION_FEATURE_DIM

Model = Union[DecoderLM, EncDecLM]


def build_model(
    cfg: ModelConfig,
    par: Parallelism = NONE_PARALLEL,
    remat: bool = False,
    unroll: bool = False,
    seq_parallel: bool = False,
) -> Model:
    if cfg.is_encdec:
        return EncDecLM(cfg, par, remat, unroll)
    return DecoderLM(cfg, par, remat, unroll, seq_parallel)


def input_specs(cfg: ModelConfig, case: ShapeCase) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStructs for the batch of one (arch x shape) cell.

    train/prefill: full (B, S) token batch (+ modality stubs).
    decode: one new token per row; the KV cache itself is part of the step
    *state* (see launch/steps.py), not the batch.
    """
    b, s = case.global_batch, case.seq_len
    i32, f32 = jnp.int32, jnp.float32
    if case.kind in ("train", "prefill"):
        specs: Dict[str, Any] = {}
        if cfg.is_encdec:
            specs["frames"] = jax.ShapeDtypeStruct(
                (b, cfg.encoder_seq, cfg.d_model), f32
            )
            specs["tokens"] = jax.ShapeDtypeStruct((b, s), i32)
        elif cfg.frontend == "vision":
            specs["patches"] = jax.ShapeDtypeStruct(
                (b, cfg.num_patches, VISION_FEATURE_DIM), f32
            )
            specs["tokens"] = jax.ShapeDtypeStruct((b, s - cfg.num_patches), i32)
        else:
            specs["tokens"] = jax.ShapeDtypeStruct((b, s), i32)
        if case.kind == "train":
            specs["loss_mask"] = jax.ShapeDtypeStruct(
                specs["tokens"].shape, f32
            )
        return specs
    # decode: one token per row + per-row cache lengths.
    return {
        "tokens": jax.ShapeDtypeStruct((b, 1), i32),
        "cache_len": jax.ShapeDtypeStruct((b,), i32),
    }


def cache_specs(cfg: ModelConfig, case: ShapeCase) -> Any:
    """ShapeDtypeStructs of the KV/state cache for a decode case."""
    model = build_model(cfg)
    return jax.eval_shape(
        lambda: model.init_cache(case.global_batch, case.seq_len)
    )


# Cache leaves holding RECURRENT state (SSM/RWKV): their post-prefill value
# depends on every input position, so right-padding a prompt corrupts them.
# Attention leaves (k/v/...) are per-position and masked by cache_len, so
# padded rows are never attended before being overwritten.
RECURRENT_CACHE_LEAVES = frozenset({"h", "conv", "state", "shift_t", "shift_c"})


def cache_leaf_names(model: Model) -> frozenset:
    """Distinct cache leaf names of a model (no device allocation)."""
    shapes = jax.eval_shape(lambda: model.init_cache(1, 8))
    names = set()

    def walk(tree):
        for k, v in tree.items():
            if isinstance(v, dict):
                walk(v)
            else:
                names.add(k)

    walk(shapes)
    return frozenset(names)


def has_recurrent_cache(model: Model) -> bool:
    """True when the model carries recurrent state in its cache, i.e.
    prompts cannot be right-padded to bucketed prefill lengths."""
    return bool(cache_leaf_names(model) & RECURRENT_CACHE_LEAVES)


# Cache leaves a block-pool (paged) layout can host: per-position attention
# K/V plus their int8 dequant scales.  Anything else (recurrent state, MLA
# latents, cross-attention memory) keeps the dense (max_batch, max_len) slab.
PAGEABLE_CACHE_LEAVES = frozenset({"k", "v", "k_scale", "v_scale"})


def cache_layout(model: Model) -> str:
    """How the serving engine should lay out this model's decode cache.

    "paged": every cache leaf is per-position attention K/V (pure-GQA
    stacks), so the engine may use the block-table pool from
    ``serving/kvcache`` with per-request max_len and chunked prefill.

    "dense": one (max_batch, max_len) slab per leaf.  Recurrent caches
    (SSM/RWKV) and token-choice MoE keep this path — the same families that
    are pad-sensitive at prefill — as do MLA latents and enc-dec cross
    caches, whose leaves are not plain paged K/V."""
    if model.cfg.is_encdec:
        return "dense"
    if not prefill_pad_safe(model):
        return "dense"
    if not cache_leaf_names(model) <= PAGEABLE_CACHE_LEAVES:
        return "dense"
    return "paged"


# ------------------------------------------------- serving cache sharding
#
# The serving engine's mesh story (launch/steps.ServingShardings): weights
# are TP-sharded over "model" via param_pspecs, while the decode cache is
# DATA-parallel — the dense slab shards over its batch (slot) dim, paged
# block pools over their block dim — and replicates over TP.  The functions
# below find the right dim STRUCTURALLY (scanned layer stacks carry leading
# repeat dims, so the axis is not fixed per leaf — and shape sniffing would
# misfire when repeats equals the probed size) and emit the cache_layout-
# aware PartitionSpec tree the engine plugs into its jit roots.


def _grown_axes(tree_a: Any, tree_b: Any) -> Any:
    """Per leaf: the single dim index whose size differs between the two
    shape probes."""
    return jax.tree.map(
        lambda a, b: next(
            i for i, (x, y) in enumerate(zip(a.shape, b.shape)) if x != y
        ),
        tree_a, tree_b,
    )


def paged_cache_block_axes(model: Model, num_blocks: int, block_size: int,
                           kv_quant: bool = False) -> Any:
    """Per-leaf block axis of the paged pools (eval_shape probe: grow
    num_blocks by one and see which dim moved)."""
    a = jax.eval_shape(lambda: model.init_paged_cache(
        num_blocks, block_size, kv_quant=kv_quant))
    b = jax.eval_shape(lambda: model.init_paged_cache(
        num_blocks + 1, block_size, kv_quant=kv_quant))
    return _grown_axes(a, b)


def dense_cache_batch_axes(model: Model, max_batch: int, max_len: int,
                           kv_quant: bool = False) -> Any:
    """Per-leaf batch (slot) axis of the dense serving slab."""
    a = jax.eval_shape(lambda: model.init_cache(
        max_batch, max_len, kv_quant=kv_quant))
    b = jax.eval_shape(lambda: model.init_cache(
        max_batch + 1, max_len, kv_quant=kv_quant))
    return _grown_axes(a, b)


def serving_cache_pspecs(model: Model, par: Parallelism, *,
                         max_batch: Optional[int] = None,
                         max_len: Optional[int] = None,
                         num_blocks: Optional[int] = None,
                         block_size: Optional[int] = None,
                         kv_quant: bool = False,
                         axes: Any = None, shapes: Any = None) -> Any:
    """cache_layout-aware PartitionSpec tree for the serving decode cache.

    Pass (num_blocks, block_size) for the paged layout — block dim sharded
    over the DP axes — or (max_batch, max_len) for the dense slab — batch
    dim sharded over the DP axes.  Dims not divisible by the DP size stay
    replicated (jit boundaries require exact divisibility), as does
    everything on the TP axis: the cache is pure data-parallel state.

    ``axes``/``shapes``: optional precomputed axis tree + cache (shape)
    tree — callers that already probed (PagedKVCache keeps its block axes)
    pass them to skip re-tracing the cache init."""
    from jax.sharding import PartitionSpec as _P

    if (num_blocks is None) == (max_batch is None):
        raise ValueError(
            "pass exactly one of num_blocks (paged) or max_batch (dense)"
        )
    if axes is None:
        axes = (paged_cache_block_axes(model, num_blocks, block_size,
                                       kv_quant=kv_quant)
                if num_blocks is not None else
                dense_cache_batch_axes(model, max_batch, max_len,
                                       kv_quant=kv_quant))
    if shapes is None:
        shapes = jax.eval_shape(
            (lambda: model.init_paged_cache(num_blocks, block_size,
                                            kv_quant=kv_quant))
            if num_blocks is not None else
            (lambda: model.init_cache(max_batch, max_len,
                                      kv_quant=kv_quant)))
    dp_size = par.dp_size

    def spec(leaf, ax):
        entries = [None] * len(leaf.shape)
        if par.mesh is not None and leaf.shape[ax] % dp_size == 0:
            entries[ax] = par.dp
        return _P(*entries)

    return jax.tree.map(spec, shapes, axes)


def prefill_pad_safe(model: Model) -> bool:
    """True when right-padding a prompt cannot change real positions'
    outputs, i.e. the serving engine may bucket prompt lengths.

    Two architecture families are pad-SENSITIVE: recurrent caches
    (SSM/RWKV state folds in every input position) and token-choice MoE
    (expert capacity is budgeted over the flattened token batch, so padding
    tokens compete for — and can evict real tokens from — expert slots).
    """
    if has_recurrent_cache(model):
        return False
    return getattr(model.cfg, "moe", None) is None


def build_draft_params(model: Model, params: Any, grams: Any, ratio: float,
                       method: str = "nsvd1") -> Any:
    """Draft construction from a compression plan: factor ``params`` at a
    HIGHER compression ratio than the serving target, yielding the
    self-speculative draft checkpoint (same architecture, cheaper matmuls
    — the factored leaves dispatch through ``linear_apply`` unchanged).

    NSVD is training-free, so the draft costs one extra ``build_plan`` +
    ``compress_params`` pass over the same calibration Grams the target's
    compression already collected — the compression sweep ships its own
    draft models for free.  Pass the result as
    ``SpecConfig(draft_params=...)`` (serving/spec)."""
    from repro.core import CompressionConfig, build_plan, compress_params

    if not 0.0 < ratio < 1.0:
        raise ValueError(f"draft compression ratio must be in (0, 1), got {ratio}")
    plan = build_plan(
        model.compressible_targets(),
        CompressionConfig(method=method, ratio=ratio, dtype="float32",
                          use_randomized=False),
    )
    return compress_params(params, plan, grams)


def param_specs(cfg: ModelConfig, seed: int = 0) -> Any:
    """ShapeDtypeStructs of the model params (no allocation)."""
    model = build_model(cfg)
    return jax.eval_shape(lambda: model.init(jax.random.key(seed)))


def count_params(cfg: ModelConfig) -> int:
    import numpy as np

    shapes = param_specs(cfg)
    return int(
        sum(np.prod(x.shape) for x in jax.tree.leaves(shapes))
    )


def count_active_params(cfg: ModelConfig) -> int:
    """Active params per token (MoE: only top_k + shared experts count)."""
    import numpy as np

    total = count_params(cfg)
    if cfg.moe is None:
        return total
    # Subtract inactive expert params.
    m = cfg.moe
    n_moe_layers = sum(1 for _, f in _specs(cfg) if f == "moe")
    per_expert = 3 * cfg.d_model * m.d_ff_expert
    inactive = n_moe_layers * (m.num_experts - m.top_k) * per_expert
    return int(total - inactive)


def _specs(cfg: ModelConfig):
    from .blocks import resolve_specs

    return resolve_specs(cfg)
