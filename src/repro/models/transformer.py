"""Decoder-only LM assembly (dense / MoE / hybrid / SSM / VLM)."""

from __future__ import annotations

from typing import Any, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.parallel.sharding import (
    NONE_PARALLEL,
    Parallelism,
    traces_with_own_parallelism,
)

from .blocks import (
    group_apply,
    group_cache_init,
    group_init,
    group_layers,
    resolve_specs,
)
from .layers import (
    embed,
    embedding_init,
    learned_pos,
    learned_pos_init,
    linear,
    linear_init,
    norm_apply,
    norm_init,
    unembed,
)

VISION_FEATURE_DIM = 1024  # CLIP-L patch feature width (llava stub input)


class DecoderLM:
    """Functional decoder-only LM over plain dict pytrees.

    apply modes: "train" (causal, no cache), "prefill" (causal, fills cache),
    "decode" (single new token per row against the cache).
    """

    def __init__(
        self,
        cfg: ModelConfig,
        par: Parallelism = NONE_PARALLEL,
        remat: bool = False,
        unroll: bool = False,
        seq_parallel: bool = False,
    ):
        self.cfg = cfg
        self.par = par
        self.remat = remat
        self.unroll = unroll
        # Sequence parallelism: residual stream sharded over the model axis
        # on the sequence dim between blocks; XLA turns the Megatron
        # all-reduce pairs into reduce-scatter + all-gather (half the wire
        # bytes, 1/TP the activation residency).  §Perf hillclimb lever.
        self.seq_parallel = seq_parallel
        self.specs = resolve_specs(cfg)
        self.groups = group_layers(self.specs)
        self.dtype = getattr(jnp, cfg.dtype)

    # ------------------------------------------------------------- params

    def init(self, key) -> Dict:
        cfg = self.cfg
        ks = jax.random.split(key, len(self.groups) + 4)
        params: Dict[str, Any] = {
            "embed": embedding_init(ks[0], cfg.vocab_size, cfg.d_model, self.dtype)
        }
        if cfg.pos_emb == "learned":
            params["pos"] = learned_pos_init(ks[1], cfg.max_seq, cfg.d_model, self.dtype)
        if cfg.frontend == "vision":
            pk = jax.random.split(ks[2], 2)
            params["projector"] = {
                "wi": linear_init(pk[0], VISION_FEATURE_DIM, cfg.d_model, self.dtype),
                "wo": linear_init(pk[1], cfg.d_model, cfg.d_model, self.dtype),
            }
        for i, g in enumerate(self.groups):
            params[f"g{i}"] = group_init(ks[3 + i], g, cfg, self.dtype, cross=False)
        params["final_norm"] = norm_init(cfg.norm, cfg.d_model, self.dtype)
        if not cfg.tie_embeddings:
            params["unembed"] = linear_init(
                ks[-1], cfg.d_model, cfg.vocab_size, self.dtype
            )
        return params

    def init_cache(self, batch: int, max_len: int, dtype=None,
                   kv_quant: bool = False) -> Dict:
        dtype = dtype or self.dtype
        return {
            f"g{i}": group_cache_init(g, self.cfg, batch, max_len, dtype,
                                      cross=False, kv_quant=kv_quant)
            for i, g in enumerate(self.groups)
        }

    def init_paged_cache(self, num_blocks: int, block_size: int, dtype=None,
                         kv_quant: bool = False) -> Dict:
        """Block-pool KV cache shared by all rows (see attention.
        init_paged_kv_cache); only valid for pure-attention stacks —
        ``models.api.cache_layout`` reports which models qualify."""
        dtype = dtype or self.dtype
        from .blocks import group_paged_cache_init

        return {
            f"g{i}": group_paged_cache_init(g, self.cfg, num_blocks,
                                            block_size, dtype, kv_quant)
            for i, g in enumerate(self.groups)
        }

    # -------------------------------------------------------------- apply

    @traces_with_own_parallelism
    def apply(
        self,
        params: Mapping[str, Any],
        tokens: jax.Array,
        *,
        patches: Optional[jax.Array] = None,
        mode: str = "train",
        cache: Optional[Dict] = None,
        cache_len: Optional[jax.Array] = None,
        block_tables: Optional[jax.Array] = None,
        taps: Optional[Dict] = None,
        output: str = "logits",
    ) -> Tuple[jax.Array, Optional[Dict], jax.Array]:
        """Returns (logits-or-hidden, new_cache, aux_loss).  output="hidden"
        skips the unembed (chunked-loss path computes it in seq chunks)."""
        cfg = self.cfg
        par = self.par
        b, s_text = tokens.shape

        x = embed(params["embed"], tokens).astype(self.dtype)
        n_prefix = 0
        if patches is not None:
            if taps is not None:
                taps["projector.in"] = patches
            pv = jax.nn.gelu(linear(params["projector"]["wi"], patches.astype(self.dtype)))
            if taps is not None:
                taps["projector.mid"] = pv
            pv = linear(params["projector"]["wo"], pv)
            x = jnp.concatenate([pv, x], axis=1)
            n_prefix = patches.shape[1]
        s = x.shape[1]

        if mode == "decode":
            assert cache_len is not None
            # (B, S): one new token per row, or an S-token chunk streaming
            # into the (paged) cache at each row's current length.
            positions = cache_len[:, None] + jnp.arange(s, dtype=jnp.int32)
        else:
            positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

        if cfg.pos_emb == "learned":
            x = x + learned_pos(params["pos"], positions).astype(x.dtype)

        seq_axis = par.tp_axis if (self.seq_parallel and mode != "decode") else None
        x = par.constrain(x, par.dp, seq_axis, None)

        new_cache: Dict[str, Any] = {}
        aux_total = jnp.zeros((), jnp.float32)
        for i, g in enumerate(self.groups):
            x, nc, aux = group_apply(
                params[f"g{i}"], x, g, cfg,
                positions=positions, mode=mode,
                cache=None if cache is None else cache.get(f"g{i}"),
                cache_len=cache_len,
                par=par, taps=taps, tap_group=f"g{i}",
                remat=self.remat and mode == "train",
                unroll=self.unroll,
                block_tables=block_tables,
            )
            x = par.constrain(x, par.dp, seq_axis, None)
            if nc is not None:
                new_cache[f"g{i}"] = nc
            aux_total = aux_total + aux

        x = norm_apply(params["final_norm"], x)
        if taps is not None:
            taps["final.out_in"] = x
        if n_prefix:
            x = x[:, n_prefix:]
        if output == "hidden":
            return x, (new_cache or None), aux_total
        logits_params = params.get("unembed", params["embed"])
        logits = unembed(logits_params, x)
        logits = par.constrain(logits, par.dp, None, "model")
        return logits, (new_cache or None), aux_total

    # ---------------------------------------------------- compressible map

    def compressible_targets(self):
        """TargetSpecs for every factorizable matrix (DESIGN.md §7)."""
        from repro.core.plan import TargetSpec

        cfg = self.cfg
        targets = []
        d = cfg.d_model
        hq = cfg.num_heads * cfg.head_dim
        hkv = cfg.num_kv_heads * cfg.head_dim if cfg.num_kv_heads else 0

        def add(path, in_dim, out_dim, tap, stacked=()):
            targets.append(
                TargetSpec(
                    path=path, in_dim=in_dim, out_dim=out_dim,
                    gram_key=tap, stacked=stacked,
                )
            )

        for i, g in enumerate(self.groups):
            rep = (g.repeats,) if g.repeats > 1 else ()
            for j, (mixer, ffn) in enumerate(g.period):
                base = (f"g{i}", f"sub{j}")
                tap = f"g{i}/sub{j}"
                if mixer == "gqa":
                    add(base + ("attn", "wq"), d, hq, f"{tap}.attn.in", rep)
                    add(base + ("attn", "wk"), d, hkv, f"{tap}.attn.in", rep)
                    add(base + ("attn", "wv"), d, hkv, f"{tap}.attn.in", rep)
                    add(base + ("attn", "wo"), hq, d, f"{tap}.attn.out_in", rep)
                elif mixer == "mla":
                    m = cfg.mla
                    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
                    add(base + ("attn", "wq_a"), d, m.q_lora_rank, f"{tap}.attn.in", rep)
                    add(base + ("attn", "wq_b"), m.q_lora_rank, cfg.num_heads * qk,
                        f"{tap}.attn.q_lora_in", rep)
                    add(base + ("attn", "wkv_a"), d, m.kv_lora_rank + m.qk_rope_head_dim,
                        f"{tap}.attn.in", rep)
                    add(base + ("attn", "wkv_b"), m.kv_lora_rank,
                        cfg.num_heads * (m.qk_nope_head_dim + m.v_head_dim),
                        f"{tap}.attn.kv_lora_in", rep)
                    add(base + ("attn", "wo"), cfg.num_heads * m.v_head_dim, d,
                        f"{tap}.attn.out_in", rep)
                elif mixer == "mamba":
                    mc = cfg.mamba
                    dt_rank = mc.dt_rank or -(-d // 16)
                    add(base + ("mamba", "in_proj"), d, 2 * mc.d_inner, f"{tap}.mamba.in", rep)
                    add(base + ("mamba", "x_proj"), mc.d_inner, dt_rank + 2 * mc.d_state,
                        f"{tap}.mamba.ssm_in", rep)
                    add(base + ("mamba", "dt_proj"), dt_rank, mc.d_inner,
                        f"{tap}.mamba.dt_in", rep)
                    add(base + ("mamba", "out_proj"), mc.d_inner, d, f"{tap}.mamba.out_in", rep)
                elif mixer == "rwkv":
                    for w, t in (("wr", "r"), ("wk", "k"), ("wv", "v"), ("wg", "g")):
                        add(base + ("rwkv_t", w), d, d, f"{tap}.rwkv_t.{t}_in", rep)
                    add(base + ("rwkv_t", "wo"), d, d, f"{tap}.rwkv_t.out_in", rep)

                if ffn == "mlp":
                    add(base + ("mlp", "wi"), d, cfg.d_ff, f"{tap}.mlp.in", rep)
                    if cfg.activation == "swiglu":
                        add(base + ("mlp", "wg"), d, cfg.d_ff, f"{tap}.mlp.in", rep)
                    add(base + ("mlp", "wo"), cfg.d_ff, d, f"{tap}.mlp.mid", rep)
                elif ffn == "moe":
                    m = cfg.moe
                    erep = rep + (m.num_experts,)
                    add(base + ("moe", "experts", "wi"), d, m.d_ff_expert,
                        f"{tap}.moe.expert_buf", erep)
                    add(base + ("moe", "experts", "wg"), d, m.d_ff_expert,
                        f"{tap}.moe.expert_buf", erep)
                    add(base + ("moe", "experts", "wo"), m.d_ff_expert, d,
                        f"{tap}.moe.expert_mid", erep)
                    if m.num_shared_experts:
                        fs = m.d_ff_expert * m.num_shared_experts
                        add(base + ("moe", "shared", "wi"), d, fs, f"{tap}.moe.shared_in", rep)
                        add(base + ("moe", "shared", "wg"), d, fs, f"{tap}.moe.shared_in", rep)
                        add(base + ("moe", "shared", "wo"), fs, d, f"{tap}.moe.shared_mid", rep)
                elif ffn == "cmix":
                    add(base + ("rwkv_c", "wk"), d, cfg.d_ff, f"{tap}.rwkv_c.k_in", rep)
                    add(base + ("rwkv_c", "wv"), cfg.d_ff, d, f"{tap}.rwkv_c.mid", rep)
                    add(base + ("rwkv_c", "wr"), d, d, f"{tap}.rwkv_c.r_in", rep)

        if cfg.frontend == "vision":
            targets.append(TargetSpec(("projector", "wi"), VISION_FEATURE_DIM, d,
                                      "projector.in"))
            targets.append(TargetSpec(("projector", "wo"), d, d, "projector.mid"))
        return targets
