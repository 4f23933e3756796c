"""Encoder-decoder LM (whisper-family) with stubbed audio frontend.

``frames`` are precomputed post-conv frame embeddings (B, T_enc, d_model)
per the assignment — the conv1d/mel frontend is a stub.  The decoder adds
cross-attention to every block; decode reuses prefilled cross K/V.
"""

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

from .blocks import StackGroup, group_apply, group_cache_init, group_init
from .layers import (
    embed,
    embedding_init,
    learned_pos,
    learned_pos_init,
    linear_init,
    norm_apply,
    norm_init,
    unembed,
)


class EncDecLM:
    def __init__(self, cfg: ModelConfig, par: Parallelism = NONE_PARALLEL,
                 remat: bool = False, unroll: bool = False):
        assert cfg.is_encdec
        self.cfg = cfg
        self.par = par
        self.remat = remat
        self.unroll = unroll
        self.dtype = getattr(jnp, cfg.dtype)
        # Encoder and decoder are each a single uniform stack.
        self.enc_group = StackGroup((("gqa", "mlp"),), cfg.encoder_layers, 0)
        self.dec_group = StackGroup((("gqa", "mlp"),), cfg.num_layers, 0)

    def init(self, key) -> Dict:
        cfg = self.cfg
        ks = jax.random.split(key, 7)
        return {
            "embed": embedding_init(ks[0], cfg.vocab_size, cfg.d_model, self.dtype),
            "pos_dec": learned_pos_init(ks[1], cfg.max_seq, cfg.d_model, self.dtype),
            "pos_enc": learned_pos_init(ks[2], cfg.encoder_seq, cfg.d_model, self.dtype),
            "encoder": group_init(ks[3], self.enc_group, cfg, self.dtype, cross=False),
            "enc_norm": norm_init(cfg.norm, cfg.d_model, self.dtype),
            "decoder": group_init(ks[4], self.dec_group, cfg, self.dtype, cross=True),
            "final_norm": norm_init(cfg.norm, cfg.d_model, self.dtype),
            "unembed": linear_init(ks[5], cfg.d_model, cfg.vocab_size, self.dtype),
        }

    def init_cache(self, batch: int, max_len: int, dtype=None,
                   kv_quant: bool = False) -> Dict:
        dtype = dtype or self.dtype
        return {
            "decoder": group_cache_init(
                self.dec_group, self.cfg, batch, max_len, dtype, cross=True,
                kv_quant=kv_quant,
            )
        }

    def encode(self, params, frames: jax.Array, taps=None) -> jax.Array:
        cfg = self.cfg
        b, t, _ = frames.shape
        pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
        x = frames.astype(self.dtype) + learned_pos(params["pos_enc"], pos).astype(
            self.dtype
        )
        x = self.par.constrain(x, self.par.dp, None, None)
        x, _, _ = group_apply(
            params["encoder"], x, self.enc_group, cfg,
            positions=pos, mode="train", par=self.par,
            taps=taps, tap_group="enc", encoder=True,
            remat=self.remat, unroll=self.unroll,
        )
        return norm_apply(params["enc_norm"], x)

    @traces_with_own_parallelism
    def apply(
        self,
        params: Mapping[str, Any],
        tokens: jax.Array,
        *,
        frames: Optional[jax.Array] = None,
        memory: Optional[jax.Array] = None,
        mode: str = "train",
        cache: Optional[Dict] = None,
        cache_len: Optional[jax.Array] = None,
        taps: Optional[Dict] = None,
    ) -> Tuple[jax.Array, Optional[Dict], jax.Array]:
        """Returns (logits, new_cache, aux).  For train/prefill pass
        ``frames``; decode uses the prefilled cross-K/V cache instead."""
        cfg = self.cfg
        b, s = tokens.shape
        if mode != "decode" and memory is None:
            memory = self.encode(params, frames, taps=taps)

        if mode == "decode":
            positions = cache_len[:, None]
        else:
            positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

        x = embed(params["embed"], tokens).astype(self.dtype)
        x = x + learned_pos(params["pos_dec"], positions).astype(x.dtype)
        x = self.par.constrain(x, self.par.dp, None, None)

        x, new_cache, aux = group_apply(
            params["decoder"], x, self.dec_group, cfg,
            positions=positions, mode=mode,
            cache=None if cache is None else cache.get("decoder"),
            cache_len=cache_len, memory=memory,
            par=self.par, taps=taps, tap_group="dec",
            remat=self.remat and mode == "train",
            unroll=self.unroll,
        )
        x = norm_apply(params["final_norm"], x)
        logits = unembed(params["unembed"], x)
        logits = self.par.constrain(logits, self.par.dp, None, "model")
        return logits, ({"decoder": new_cache} if new_cache is not None else None), aux

    def compressible_targets(self):
        from repro.core.plan import TargetSpec

        cfg = self.cfg
        d = cfg.d_model
        hq = cfg.num_heads * cfg.head_dim
        targets = []

        def add(path, in_dim, out_dim, tap, stacked):
            targets.append(TargetSpec(path=path, in_dim=in_dim, out_dim=out_dim,
                                      gram_key=tap, stacked=stacked))

        for side, group, n in (
            ("encoder", self.enc_group, cfg.encoder_layers),
            ("decoder", self.dec_group, cfg.num_layers),
        ):
            tapg = "enc" if side == "encoder" else "dec"
            rep = (n,) if n > 1 else ()
            base = (side,) if n == 1 else (side,)
            tap = f"{tapg}/sub0"
            add(base + ("sub0", "attn", "wq"), d, hq, f"{tap}.attn.in", rep)
            add(base + ("sub0", "attn", "wk"), d, hq, f"{tap}.attn.in", rep)
            add(base + ("sub0", "attn", "wv"), d, hq, f"{tap}.attn.in", rep)
            add(base + ("sub0", "attn", "wo"), hq, d, f"{tap}.attn.out_in", rep)
            if side == "decoder":
                add(base + ("sub0", "cross", "wq"), d, hq, f"{tap}.cross.in", rep)
                add(base + ("sub0", "cross", "wk"), d, hq, f"{tap}.cross.kv_in", rep)
                add(base + ("sub0", "cross", "wv"), d, hq, f"{tap}.cross.kv_in", rep)
                add(base + ("sub0", "cross", "wo"), hq, d, f"{tap}.cross.out_in", rep)
            add(base + ("sub0", "mlp", "wi"), d, cfg.d_ff, f"{tap}.mlp.in", rep)
            add(base + ("sub0", "mlp", "wo"), cfg.d_ff, d, f"{tap}.mlp.mid", rep)
        return targets
