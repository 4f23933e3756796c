"""jit'd wrapper: backend dispatch for paged-attention decode.

The Pallas kernel streams exactly the live KV pages on TPU; every other
backend (and the dry-run lowering) uses the jnp gather oracle, which is also
the bit-reference the serving equivalence tests pin against the dense-slab
decode path.  ``interpret=True`` forces the kernel body through the Pallas
interpreter (correctness tests on CPU)."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import routes
from repro.parallel.sharding import tracing_parallelism

from .paged_attention import default_rows_per_pack  # noqa: F401 (re-export)
from .paged_attention import paged_attention as _kernel_call
from .ref import (  # noqa: F401 (re-export)
    gather_pages,
    paged_attention_packed_ref,
    paged_attention_ref,
)


def paged_attention(
    q,
    k_pages,
    v_pages,
    block_tables,
    lengths,
    k_scales=None,
    v_scales=None,
    scale: Optional[float] = None,
    interpret: bool = False,
    use_kernel: Optional[bool] = None,
    rows_per_pack: Optional[int] = None,
):
    """Public op; see ref.paged_attention_ref for the argument contract.

    ``use_kernel=None`` picks the Pallas kernel on TPU and the oracle
    elsewhere; pass True/False to force either side.  ``rows_per_pack``
    sets the kernel's decode-row packing (None = auto: fill the 8-sublane
    score tile, see paged_attention.default_rows_per_pack); the oracle
    path ignores it — packing is a tiling choice, not a math change."""
    if use_kernel is None:
        use_kernel = interpret or jax.default_backend() == "tpu"
    routes.note("paged_attention", "pallas" if use_kernel else "xla")
    if not use_kernel:
        return paged_attention_ref(
            q, k_pages, v_pages, block_tables, lengths, k_scales, v_scales, scale
        )
    kernel = functools.partial(_kernel_call, scale=scale, interpret=interpret,
                               rows_per_pack=rows_per_pack)
    scales = () if k_scales is None else (k_scales, v_scales)
    par = tracing_parallelism()
    if par.active:
        return _per_device(par, kernel, q, k_pages, v_pages, block_tables,
                           lengths, *scales)
    return kernel(q, k_pages, v_pages, block_tables, lengths, *scales)


def _per_device(par, kernel, q, k_pages, v_pages, block_tables, lengths,
                *scales):
    """Run the kernel on every device of ``par``'s mesh in a shard_map (a
    Mosaic kernel cannot be partitioned automatically).

    Rows and pool blocks split over the DP axes when both divide: the
    serving engine then pins each slot's blocks to its own DP shard's
    contiguous block range, so ids become local by subtracting the shard's
    first block.  Otherwise every input is replicated.  Pools are
    replicated over TP, so each TP shard attends every head."""
    from jax.sharding import PartitionSpec as P

    n_dp = par.dp_size
    n_blocks = k_pages.shape[0]
    split = (n_dp > 1 and q.shape[0] % n_dp == 0
             and n_blocks % n_dp == 0)
    dp = par.dp if split else None

    def spec(x):
        return P(dp, *([None] * (x.ndim - 1)))

    def local(q, kp, vp, bt, ln, *sc):
        if split:
            first = jax.lax.axis_index(par.dp) * (n_blocks // n_dp)
            bt = jnp.where(bt >= 0, bt - first, bt)
        return kernel(q, kp, vp, bt, ln, *sc)

    args = (q, k_pages, v_pages, block_tables, lengths, *scales)
    return jax.shard_map(
        local, mesh=par.mesh, in_specs=tuple(spec(a) for a in args),
        out_specs=spec(q), check_vma=False,
    )(*args)
