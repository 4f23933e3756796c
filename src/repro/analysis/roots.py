"""Trace serving jit roots to lowered/compiled artifacts from abstract
inputs.

The registry (launch/steps.serving_root_registry) supplies the builder,
donate_argnums, abstract input avals and sharding hook for every root; this
module jits each root exactly the way the engine does (same donation, same
pinned shardings) and lowers it with ShapeDtypeStructs — so the audited
computation is byte-for-byte the one a running engine would execute, but
nothing is allocated and no step runs.

Spec roots take DRAFT params as arg 0; the auditor traces them with the
TARGET's param avals (identical architecture — any well-formed params
pytree for the model lowers the same ops), which keeps the audit free of a
compression pass."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax

from repro.launch.steps import (
    RootContext,
    RootSpec,
    ServingShardings,
    named,
    serving_root_registry,
)
from repro.models.api import (
    cache_layout,
    paged_cache_block_axes,
    prefill_pad_safe,
    serving_cache_pspecs,
)


@dataclasses.dataclass
class RootArtifact:
    """One traced serving root: everything the audits consume."""

    spec: RootSpec
    ctx: RootContext
    args: Tuple[Any, ...]            # positional aval pytrees
    out_avals: Any                   # output aval pytree (tuple of trees)
    jaxpr: Any                       # ClosedJaxpr of the unjitted fn
    lowered: Any                     # jax.stages.Lowered
    compiled: Any                    # jax.stages.Compiled (None if skipped)
    expected_shardings: Optional[Tuple[Any, Any]]  # (in, out) pins or None

    @property
    def name(self) -> str:
        return self.spec.name


def make_root_context(model, *, par=None, max_batch: int = 8,
                      max_len: int = 256, kv_quant: bool = False,
                      prefill_chunk: int = 64, block_size: int = 16,
                      num_blocks: Optional[int] = None,
                      spec_k: int = 4, bucket: int = 16) -> RootContext:
    """A RootContext mirroring ServingEngine's geometry resolution: DP
    shard count falls back to 1 when max_batch doesn't divide DP (the
    engine then keeps slots/pools replicated), and bucketed admission
    follows prefill_pad_safe."""
    dp_shards = 1
    if par is not None and getattr(par, "active", False):
        dp_shards = par.dp_size if max_batch % par.dp_size == 0 else 1
    return RootContext(
        model=model, max_batch=max_batch, max_len=max_len,
        kv_quant=kv_quant, prefill_chunk=prefill_chunk,
        block_size=block_size, num_blocks=num_blocks, spec_k=spec_k,
        bucket=bucket, bucketed=prefill_pad_safe(model),
        dp_shards=dp_shards,
    )


def make_shardings(ctx: RootContext, layout: str, params_avals,
                   par) -> ServingShardings:
    """The ServingShardings bundle the engine would pin for this geometry
    (paged pools over their block dim when slots divide DP, else
    replicated; dense slab over its batch dim)."""
    from jax.sharding import PartitionSpec as P

    model = ctx.model
    if layout == "paged":
        pools = ctx.pool_avals()
        if ctx.dp_shards > 1:
            axes = paged_cache_block_axes(model, ctx.resolved_num_blocks,
                                          ctx.block_size,
                                          kv_quant=ctx.kv_quant)
            pspecs = serving_cache_pspecs(
                model, par, num_blocks=ctx.resolved_num_blocks,
                block_size=ctx.block_size, kv_quant=ctx.kv_quant,
                axes=axes, shapes=pools,
            )
        else:
            pspecs = jax.tree.map(lambda leaf: P(), pools)
        cache_sh = named(pspecs, par.mesh)
    else:
        cache = ctx.cache_avals()
        cache_sh = named(
            serving_cache_pspecs(model, par, max_batch=ctx.max_batch,
                                 max_len=ctx.max_len,
                                 kv_quant=ctx.kv_quant, shapes=cache),
            par.mesh,
        )
    return ServingShardings(par, params_avals, cache_sh, ctx.max_batch)


def trace_root(spec: RootSpec, ctx: RootContext, params_avals,
               sh: Optional[ServingShardings] = None,
               compile: bool = True) -> RootArtifact:
    """Lower (and compile) one root exactly as the engine jits it."""
    args = spec.abstract_inputs(ctx, params_avals)
    fn = spec.build(ctx)
    sh_pair = None
    kw: Dict[str, Any] = {}
    if sh is not None:
        draft_sh = sh.params if spec.needs_draft else None
        sh_pair = spec.shardings(sh, ctx, draft_sh)
        kw = {"in_shardings": sh_pair[0], "out_shardings": sh_pair[1]}
    lowered = jax.jit(fn, donate_argnums=spec.donate, **kw).lower(*args)
    compiled = lowered.compile() if compile else None
    jaxpr = jax.make_jaxpr(fn)(*args)
    out_avals = jax.eval_shape(fn, *args)
    return RootArtifact(spec=spec, ctx=ctx, args=args, out_avals=out_avals,
                        jaxpr=jaxpr, lowered=lowered, compiled=compiled,
                        expected_shardings=sh_pair)


def audit_roots(model, params_avals, *, par=None, layout: Optional[str] = None,
                spec: bool = True, compile: bool = True,
                **ctx_kw) -> List[RootArtifact]:
    """Trace every registry root for one cache layout.  ``layout=None``
    resolves the model's native layout; ``spec`` adds the speculative roots
    when the model supports them (paged-capable caches only, matching the
    engine's constructor check)."""
    native = cache_layout(model)
    layout = layout or native
    if layout == "paged" and native != "paged":
        raise ValueError(
            f"model {model.cfg.name!r} has cache layout {native!r}; "
            "cannot audit paged roots"
        )
    spec = spec and native == "paged"  # spec roots need paged-capable caches
    ctx = make_root_context(model, par=par, **ctx_kw)
    sh = None
    if par is not None and getattr(par, "active", False):
        sh = make_shardings(ctx, layout, params_avals, par)
    return [trace_root(r, ctx, params_avals, sh, compile=compile)
            for r in serving_root_registry(layout, spec=spec)]
