"""jit'd wrapper: padding + dispatch (kernel on TPU, oracle elsewhere)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import routes
from repro.parallel.sharding import tracing_parallelism

from .nested_lowrank import (
    VMEM_LIMIT_BYTES,
    kernel_vmem_bytes,
    nested_lowrank_matmul as _kernel_call,
)
from .ref import nested_lowrank_matmul_ref


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


# The kernel keeps x and both rank-k intermediates resident in VMEM, so it
# only pays off (and only fits the ~16 MB budget) for decode-shaped row
# counts; larger batches (prefill/train) stay on the XLA matmul path.
MAX_KERNEL_ROWS = 1024


def takes_kernel(x_shape, x_dtype, u_shape, v_shape, u2_shape,
                 block_n: int = 256) -> bool:
    """The automatic route: the Pallas kernel on TPU for decode-shaped
    inputs (flattened rows <= MAX_KERNEL_ROWS) whose tiles fit VMEM, the
    XLA matmul path otherwise.  The VMEM gate matters because the
    resident u/u2 tiles scale with the decomposition rank, so a mildly-
    compressed wide layer (rank of order d_model/2) overflows VMEM even at
    decode row counts.  Under tensor parallelism the factors are sharded
    on their rank-side inputs and a psum must sit between the two matmuls,
    which the fused kernel cannot hold: XLA places it."""
    rows = 1
    for s in x_shape[:-1]:
        rows *= s
    return (jax.default_backend() == "tpu"
            and tracing_parallelism().tp_size == 1
            and rows <= MAX_KERNEL_ROWS
            and kernel_vmem_bytes(
                rows, x_shape[-1], v_shape[-1], u_shape[-1], u2_shape[-1],
                block_n=min(block_n, v_shape[-1]),
                dtype=str(x_dtype)) <= VMEM_LIMIT_BYTES)


def nested_lowrank_matmul(
    x, u, v, u2, v2, block_n: int = 256, interpret: bool = False,
    use_kernel: bool | None = None,
):
    """Public op.  On non-TPU backends (and under dry-run lowering) the
    pure-jnp oracle is used; interpret=True forces the kernel body through
    the Pallas interpreter (correctness tests).  ``use_kernel=None`` takes
    the route ``takes_kernel`` picks; pass True to force the kernel."""
    if use_kernel is None:
        use_kernel = interpret or takes_kernel(
            x.shape, x.dtype, u.shape, v.shape, u2.shape, block_n)
    routes.note("nested_lowrank", "pallas" if use_kernel else "xla")
    if not use_kernel:
        return nested_lowrank_matmul_ref(x, u, v, u2, v2)
    n = v.shape[-1]
    bn = min(block_n, n)
    v_p, pad_n = _pad_to(v, bn, -1)
    v2_p, _ = _pad_to(v2, bn, -1)
    kernel = functools.partial(_kernel_call, block_n=bn, interpret=interpret)
    par = tracing_parallelism()
    if par.active:
        # A Mosaic kernel cannot be partitioned automatically: run it per
        # device, rows split over DP when they divide, factors replicated.
        from jax.sharding import PartitionSpec as P

        split = par.dp_size > 1 and x.shape[0] % par.dp_size == 0
        x_spec = P(par.dp if split else None, *([None] * (x.ndim - 1)))
        kernel = jax.shard_map(kernel, mesh=par.mesh,
                               in_specs=(x_spec, P(), P(), P(), P()),
                               out_specs=x_spec, check_vma=False)
    y = kernel(x, u, v_p, u2, v2_p)
    if pad_n:
        y = y[..., : n]
    return y
