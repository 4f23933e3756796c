"""Block assembly + scan-over-layers stacking.

A decoder layer is (mixer, ffn) with pre-norm residuals:

    x = x + mixer(norm(x))          mixer in {gqa, mla, mamba, rwkv}
    [x = x + cross_attn(norm(x))]   (enc-dec decoder only)
    x = x + ffn(norm(x))            ffn in {mlp, moe, cmix}

Layers with identical specs are *stacked* (params get a leading dim) and run
under ``jax.lax.scan`` — keeping HLO size O(distinct layer kinds), which is
what makes compiling 61-layer deepseek-v3 for 512 SPMD partitions tractable.
``group_layers`` finds a (prefix, period) decomposition so interleaved
patterns (jamba's 1:7 mamba:attn, deepseek-v3's 3 dense + 58 MoE) stay
scannable.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.parallel.sharding import (
    NONE_PARALLEL,
    Parallelism,
    param_pspecs,
    tracing_with,
)

from . import attention as attn_mod
from . import mamba as mamba_mod
from . import mla as mla_mod
from . import moe as moe_mod
from . import rwkv6 as rwkv_mod
from .layers import mlp_apply, mlp_init, mlp_taps, norm_apply, norm_init

BlockSpec = Tuple[str, str]  # (mixer, ffn)


def resolve_specs(cfg: ModelConfig) -> Tuple[BlockSpec, ...]:
    """Resolve config-level layer specs to concrete (mixer, ffn) pairs."""
    out = []
    for mixer, ffn in cfg.layer_specs():
        if mixer == "attn":
            mixer = "mla" if cfg.attention == "mla" else "gqa"
        if mixer == "rwkv":
            ffn = "cmix"
        out.append((mixer, ffn))
    return tuple(out)


@dataclasses.dataclass(frozen=True)
class StackGroup:
    period: Tuple[BlockSpec, ...]
    repeats: int
    first_layer: int

    @property
    def num_layers(self) -> int:
        return len(self.period) * self.repeats


def group_layers(specs: Sequence[BlockSpec], max_prefix: int = 8) -> List[StackGroup]:
    """Decompose layer specs into [prefix runs] + [periodic scan group]."""
    n = len(specs)
    best = None  # (cost, prefix, q)
    for prefix in range(0, min(max_prefix, n) + 1):
        rem = n - prefix
        if rem == 0:
            cand = (prefix, prefix, 0)
        else:
            q = None
            for qq in range(1, rem + 1):
                if rem % qq == 0 and all(
                    specs[prefix + i] == specs[prefix + (i % qq)] for i in range(rem)
                ):
                    q = qq
                    break
            cand = (prefix + q, prefix, q)
        if best is None or cand[0] < best[0]:
            best = cand
    _, prefix, q = best
    groups: List[StackGroup] = []
    # Prefix: runs of identical specs.
    i = 0
    while i < prefix:
        j = i
        while j < prefix and specs[j] == specs[i]:
            j += 1
        groups.append(StackGroup((specs[i],), j - i, i))
        i = j
    if q:
        groups.append(StackGroup(tuple(specs[prefix : prefix + q]), (n - prefix) // q, prefix))
    return groups


# ------------------------------------------------------------- single block

def block_init(key, spec: BlockSpec, cfg: ModelConfig, dtype, cross: bool = False) -> Dict:
    mixer, ffn = spec
    ks = jax.random.split(key, 6)
    p: Dict[str, Any] = {"norm1": norm_init(cfg.norm, cfg.d_model, dtype)}
    if mixer == "gqa":
        p["attn"] = attn_mod.attention_init(ks[0], cfg, dtype)
    elif mixer == "mla":
        p["attn"] = mla_mod.mla_init(ks[0], cfg, dtype)
    elif mixer == "mamba":
        p["mamba"] = mamba_mod.mamba_init(ks[0], cfg, dtype)
    elif mixer == "rwkv":
        p["rwkv_t"] = rwkv_mod.rwkv_time_mix_init(ks[0], cfg, dtype)
    else:
        raise ValueError(mixer)
    if cross:
        p["norm_cross"] = norm_init(cfg.norm, cfg.d_model, dtype)
        p["cross"] = attn_mod.attention_init(ks[2], cfg, dtype)
    p["norm2"] = norm_init(cfg.norm, cfg.d_model, dtype)
    if ffn == "mlp":
        p["mlp"] = mlp_init(ks[1], cfg.activation, cfg.d_model, cfg.d_ff, dtype)
    elif ffn == "moe":
        p["moe"] = moe_mod.moe_init(ks[1], cfg, dtype)
    elif ffn == "cmix":
        p["rwkv_c"] = rwkv_mod.rwkv_channel_mix_init(ks[1], cfg, dtype)
    else:
        raise ValueError(ffn)
    return p


def block_cache_init(
    spec: BlockSpec, cfg: ModelConfig, batch: int, max_len: int, dtype,
    cross: bool, kv_quant: bool = False,
) -> Dict:
    mixer, _ = spec
    c: Dict[str, Any] = {}
    if mixer == "gqa":
        c["attn"] = attn_mod.init_kv_cache(cfg, batch, max_len, dtype, quant=kv_quant)
    elif mixer == "mla":
        c["attn"] = mla_mod.init_mla_cache(cfg, batch, max_len, dtype)
    elif mixer == "mamba":
        c["mamba"] = mamba_mod.init_mamba_cache(cfg, batch, dtype)
    elif mixer == "rwkv":
        c["rwkv"] = rwkv_mod.init_rwkv_cache(cfg, batch, dtype)
    if cross:
        c["cross"] = attn_mod.init_kv_cache(cfg, batch, cfg.encoder_seq, dtype)
    return c


def block_paged_cache_init(
    spec: BlockSpec, cfg: ModelConfig, num_blocks: int, block_size: int,
    dtype, kv_quant: bool = False,
) -> Dict:
    mixer, _ = spec
    if mixer != "gqa":
        raise ValueError(
            f"paged KV cache requires attention (gqa) layers, got {mixer!r}; "
            "see models.api.cache_layout"
        )
    return {
        "attn": attn_mod.init_paged_kv_cache(
            cfg, num_blocks, block_size, dtype, quant=kv_quant
        )
    }


def group_paged_cache_init(
    group: StackGroup, cfg: ModelConfig, num_blocks: int, block_size: int,
    dtype, kv_quant: bool = False,
) -> Dict:
    c = {
        f"sub{j}": block_paged_cache_init(
            spec, cfg, num_blocks, block_size, dtype, kv_quant
        )
        for j, spec in enumerate(group.period)
    }
    if group.repeats == 1:
        return c
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (group.repeats, *x.shape)), c
    )


def _moe_ffn(params_moe, h, cfg, par: Parallelism, taps, tp):
    """Dispatch MoE densely (single device) or via the EP shard_map."""
    if not par.active:
        return moe_mod.moe_apply(params_moe, h, cfg, ep_axis=None, taps=taps, tap_prefix=tp)
    assert taps is None, "taps unsupported under expert-parallel shard_map"
    from jax.sharding import PartitionSpec as P

    moe_in_specs = param_pspecs(jax.tree.map(lambda x: x, params_moe))
    # batch=1 long-context cells can't shard batch over DP — replicate
    # (each data shard redundantly routes the single row; EP still splits
    # the expert compute over the model axis).
    x_spec = (P(par.dp, None, None) if h.shape[0] % par.dp_size == 0
              else P(None, None, None))

    def inner(p, xx):
        # Per-device code: kernels inside need no partitioning of their own.
        with tracing_with(NONE_PARALLEL):
            out, aux = moe_mod.moe_apply(p, xx, cfg, ep_axis=par.tp_axis)
        aux = jax.lax.pmean(aux, par.dp_axes)
        return out, aux

    out, aux = jax.shard_map(
        inner,
        mesh=par.mesh,
        in_specs=(moe_in_specs, x_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(params_moe, h)
    return out, aux


def block_apply(
    params: Mapping[str, Any],
    x: jax.Array,
    spec: BlockSpec,
    cfg: ModelConfig,
    *,
    positions: jax.Array,
    mode: str,
    cache: Optional[Dict] = None,
    cache_len: Optional[jax.Array] = None,
    memory: Optional[jax.Array] = None,
    par: Parallelism = NONE_PARALLEL,
    taps: Optional[Dict] = None,
    tap_prefix: str = "",
    encoder: bool = False,
    block_tables: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Dict], jax.Array]:
    """Returns (x, new_cache, aux_loss)."""
    mixer, ffn = spec
    if block_tables is not None and mixer != "gqa":
        raise ValueError(f"paged decode unsupported for mixer {mixer!r}")
    aux = jnp.zeros((), jnp.float32)
    new_cache: Dict[str, Any] = {}

    h = norm_apply(params["norm1"], x)
    if mixer == "gqa":
        attn_mode = "bidir" if encoder else ("decode" if mode == "decode" else "causal")
        y, c = attn_mod.attention_apply(
            params["attn"], h, cfg, positions,
            mode=attn_mode,
            cache=None if cache is None else cache.get("attn"),
            cache_len=cache_len,
            taps=taps, tap_prefix=f"{tap_prefix}.attn",
            block_tables=block_tables,
        )
        if c is not None:
            new_cache["attn"] = c
    elif mixer == "mla":
        y, c = mla_mod.mla_apply(
            params["attn"], h, cfg, positions,
            mode="decode" if mode == "decode" else "causal",
            cache=None if cache is None else cache.get("attn"),
            cache_len=cache_len,
            taps=taps, tap_prefix=f"{tap_prefix}.attn",
        )
        if c is not None:
            new_cache["attn"] = c
    elif mixer == "mamba":
        y, c = mamba_mod.mamba_apply(
            params["mamba"], h, cfg,
            mode="decode" if mode == "decode" else "causal",
            cache=None if cache is None else cache.get("mamba"),
            taps=taps, tap_prefix=f"{tap_prefix}.mamba",
        )
        if c is not None:
            new_cache["mamba"] = c
    elif mixer == "rwkv":
        y, c = rwkv_mod.rwkv_time_mix(
            params["rwkv_t"], h, cfg,
            mode="decode" if mode == "decode" else "causal",
            cache=None if cache is None else cache.get("rwkv"),
            taps=taps, tap_prefix=f"{tap_prefix}.rwkv_t",
        )
        if c is not None:
            new_cache["rwkv"] = dict(c)
    else:
        raise ValueError(mixer)
    x = x + y

    if "cross" in params:
        h = norm_apply(params["norm_cross"], x)
        if mode == "decode":
            # Cached cross K/V (computed at prefill) — attend directly.
            y, _ = _cross_cached(params["cross"], h, cfg, cache["cross"])
            # Pass the (donated) cross cache through so the cache pytree
            # keeps its structure across decode steps.
            new_cache["cross"] = cache["cross"]
        else:
            y, ckv = attn_mod.attention_apply(
                params["cross"], h, cfg, positions, mode="cross", memory=memory,
                taps=taps, tap_prefix=f"{tap_prefix}.cross",
            )
            if cache is not None:
                new_cache["cross"] = _build_cross_cache(params["cross"], memory, cfg)
        x = x + y

    h = norm_apply(params["norm2"], x)
    if ffn == "mlp":
        if taps is not None:
            y = mlp_taps(params["mlp"], h, cfg.activation, taps, f"{tap_prefix}.mlp")
        else:
            y = mlp_apply(params["mlp"], h, cfg.activation)
    elif ffn == "moe":
        y, aux = _moe_ffn(params["moe"], h, cfg, par, taps, f"{tap_prefix}.moe")
    elif ffn == "cmix":
        y, c = rwkv_mod.rwkv_channel_mix(
            params["rwkv_c"], h, cfg,
            mode="decode" if mode == "decode" else "causal",
            cache=None if cache is None else cache.get("rwkv"),
            taps=taps, tap_prefix=f"{tap_prefix}.rwkv_c",
        )
        if c is not None:
            new_cache.setdefault("rwkv", {}).update(c)
    else:
        raise ValueError(ffn)
    x = x + y
    return x, (new_cache if new_cache else None), aux


def _build_cross_cache(params, memory, cfg: ModelConfig) -> Dict:
    """Precompute cross-attention K/V from encoder memory (decode reuse)."""
    from .attention import _split_heads
    from .layers import linear

    k = _split_heads(linear(params["wk"], memory), cfg.num_kv_heads, cfg.head_dim)
    v = _split_heads(linear(params["wv"], memory), cfg.num_kv_heads, cfg.head_dim)
    return {"k": k, "v": v}


def _cross_cached(params, x, cfg: ModelConfig, cross_cache):
    """Decode-time cross-attention against the prefilled K/V."""
    import math

    from .attention import _gqa_out, _gqa_scores, _split_heads
    from .layers import linear

    q = _split_heads(linear(params["wq"], x), cfg.num_heads, cfg.head_dim)
    k, v = cross_cache["k"], cross_cache["v"]
    scale = 1.0 / math.sqrt(cfg.head_dim)
    scores = _gqa_scores(q, k, scale)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _gqa_out(probs, v)
    y = linear(params["wo"], out.reshape(*x.shape[:-1], -1))
    return y, None


# ------------------------------------------------------------ stacked groups

def group_init(key, group: StackGroup, cfg: ModelConfig, dtype, cross: bool) -> Dict:
    """Stacked params: {"sub{j}": stacked block params} with leading repeats."""

    def one(k):
        ks = jax.random.split(k, len(group.period))
        return {
            f"sub{j}": block_init(ks[j], spec, cfg, dtype, cross)
            for j, spec in enumerate(group.period)
        }

    if group.repeats == 1:
        return one(key)
    keys = jax.random.split(key, group.repeats)
    return jax.vmap(one)(keys)


def group_cache_init(
    group: StackGroup, cfg: ModelConfig, batch: int, max_len: int, dtype,
    cross: bool, kv_quant: bool = False,
) -> Dict:
    def one():
        return {
            f"sub{j}": block_cache_init(spec, cfg, batch, max_len, dtype, cross,
                                        kv_quant)
            for j, spec in enumerate(group.period)
        }

    c = one()
    if group.repeats == 1:
        return c
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (group.repeats, *x.shape)), c
    )


def group_apply(
    params: Mapping[str, Any],
    x: jax.Array,
    group: StackGroup,
    cfg: ModelConfig,
    *,
    positions,
    mode: str,
    cache=None,
    cache_len=None,
    memory=None,
    par: Parallelism = NONE_PARALLEL,
    taps: Optional[Dict] = None,
    tap_group: str = "",
    encoder: bool = False,
    remat: bool = False,
    unroll: bool = False,
    block_tables: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Dict], jax.Array]:
    """Apply a stack group; scans when repeats > 1.  ``unroll=True`` fully
    unrolls the layer scan (roofline mode: exact HLO flop accounting —
    cost_analysis counts a while body once; see launch/roofline.py)."""

    def apply_period(p, xx, cc, layer_tag: Optional[str]):
        new_caches = {}
        aux_total = jnp.zeros((), jnp.float32)
        for j, spec in enumerate(group.period):
            tp = f"{tap_group}/{layer_tag}/sub{j}" if layer_tag is not None else f"{tap_group}/sub{j}"
            xx, nc, aux = block_apply(
                p[f"sub{j}"], xx, spec, cfg,
                positions=positions, mode=mode,
                cache=None if cc is None else cc.get(f"sub{j}"),
                cache_len=cache_len, memory=memory, par=par,
                taps=taps, tap_prefix=tp, encoder=encoder,
                block_tables=block_tables,
            )
            if nc is not None:
                new_caches[f"sub{j}"] = nc
            aux_total = aux_total + aux
        return xx, (new_caches if new_caches else None), aux_total

    if group.repeats == 1:
        return apply_period(params, x, cache, None)

    if taps is not None:
        # Calibration path: unroll so per-layer taps stay addressable.
        new_cache_list = []
        aux_total = jnp.zeros((), jnp.float32)
        for r in range(group.repeats):
            p_r = jax.tree.map(lambda t: t[r], params)
            c_r = None if cache is None else jax.tree.map(lambda t: t[r], cache)
            x, nc, aux = apply_period(p_r, x, c_r, f"rep{r}")
            aux_total = aux_total + aux
            new_cache_list.append(nc)
        new_cache = None
        if new_cache_list[0] is not None:
            new_cache = jax.tree.map(lambda *ts: jnp.stack(ts), *new_cache_list)
        return x, new_cache, aux_total

    def body(carry, xs):
        xx = carry
        p, cc = xs
        xx, nc, aux = apply_period(p, xx, cc, None)
        return xx, (nc, aux)

    if remat:
        body = jax.checkpoint(body)

    x, (new_cache, auxs) = jax.lax.scan(
        body, x, (params, cache), unroll=group.repeats if unroll else 1
    )
    return x, new_cache, jnp.sum(auxs)
