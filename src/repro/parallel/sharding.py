"""Sharding rules: DP x TP (x EP) partition specs for every param & activation.

Mesh axes:
  single pod: ("data", "model")           — 16 x 16 (v5e pod of 256)
  multi-pod:  ("pod", "data", "model")    — pods compose with data for DP;
                                            scaling to 1000+ nodes = growing
                                            "pod" (pure DP replication), so
                                            these specs are topology-stable.

Param rules (Megatron-style TP over "model"):
  embeddings (V, D)           -> (tp, None)        vocab-sharded
  unembed    (D, V)           -> (None, tp)
  attn  wq/wk/wv (D, H*hd)    -> (None, tp)        head-sharded (GSPMD pads
                                                   non-divisible head counts)
  attn  wo (H*hd, D)          -> (tp, None)        row-parallel (psum)
  mlp   wi/wg (D, F)          -> (None, tp)
  mlp   wo (F, D)             -> (tp, None)
  moe   experts (E, D, F)     -> (tp, None, None)  expert-parallel
  mamba column/row splits over d_inner; rwkv over heads.

Factored (compressed) params inherit the dense kernel's boundary shardings:
  u  (in, k)  -> (in_axis, None)
  v  (k, out) -> (None, out_axis)
so a row-parallel factored layer all-reduces a rank-k partial instead of the
full d_model — the compression shrinks the TP collective (EXPERIMENTS.md
§Perf).

Optimizer state (ZeRO-1): moments additionally sharded over the DP axes on
their largest replicated dim — see repro/optim/adamw.py.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools
import re
from typing import Any, Dict, Mapping, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class Parallelism:
    """Carries the mesh + axis names through model construction."""

    mesh: Optional[Mesh] = None
    dp_axes: Tuple[str, ...] = ("data",)
    tp_axis: Optional[str] = "model"

    @property
    def active(self) -> bool:
        return self.mesh is not None

    @property
    def dp(self):  # spec entry for batch dims
        return self.dp_axes if len(self.dp_axes) > 1 else self.dp_axes[0]

    @property
    def dp_size(self) -> int:
        n = 1
        for a in self.dp_axes if self.active else ():
            n *= self.mesh.shape[a]
        return n

    @property
    def tp_size(self) -> int:
        if not self.active or self.tp_axis is None:
            return 1
        return self.mesh.shape[self.tp_axis]

    def constrain(self, x: jax.Array, *spec) -> jax.Array:
        if not self.active:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*spec))
        )


NONE_PARALLEL = Parallelism()

# The Parallelism whose mesh XLA partitions the computation being traced,
# set by the model facades around ``apply``.  Mosaic (Pallas TPU) kernels
# cannot be partitioned automatically: their ops wrappers read this to run
# the kernel per device in a shard_map, or to take the XLA path where the
# kernel has no per-device form.
_TRACING = contextvars.ContextVar("tracing_parallelism",
                                  default=NONE_PARALLEL)


@contextlib.contextmanager
def tracing_with(par: Parallelism):
    token = _TRACING.set(par)
    try:
        yield
    finally:
        _TRACING.reset(token)


def tracing_parallelism() -> Parallelism:
    return _TRACING.get()


def traces_with_own_parallelism(apply):
    """Decorator for a model facade's ``apply``: trace it under the
    facade's own ``par`` (see ``tracing_parallelism``)."""

    @functools.wraps(apply)
    def wrapped(self, *args, **kwargs):
        with tracing_with(self.par):
            return apply(self, *args, **kwargs)

    return wrapped


def make_parallelism(mesh: Optional[Mesh]) -> Parallelism:
    if mesh is None:
        return NONE_PARALLEL
    names = mesh.axis_names
    if "pod" in names:
        return Parallelism(mesh, ("pod", "data"), "model")
    return Parallelism(mesh, ("data",), "model")


# --------------------------------------------------------------- param rules

# (path regex) -> (in_axis, out_axis) for linear-like leaves.  Specific
# rules MUST precede generic ones (first match wins).
_LINEAR_RULES: Sequence[Tuple[str, Tuple[Optional[str], Optional[str]]]] = (
    # rwkv / mamba / moe / mla specifics first
    (r"rwkv_c/wk$", (None, "model")),
    (r"rwkv_c/wv$", ("model", None)),
    (r"rwkv_c/wr$", (None, None)),
    (r"rwkv_t/(wr|wk|wv|wg)$", (None, "model")),
    (r"rwkv_t/wo$", ("model", None)),
    (r"mamba/in_proj$", (None, "model")),
    (r"mamba/x_proj$", ("model", None)),
    (r"mamba/out_proj$", ("model", None)),
    (r"(^|/)router$", (None, None)),
    (r"(^|/)wq_a$", (None, None)),
    (r"(^|/)wq_b$", (None, "model")),
    (r"(^|/)wkv_a$", (None, None)),
    (r"(^|/)wkv_b$", (None, "model")),
    # generic transformer projections
    (r"(^|/)unembed$", (None, "model")),
    (r"(^|/)(wq|wk|wv)$", (None, "model")),
    (r"(^|/)wo$", ("model", None)),
    (r"(^|/)(wi|wg)$", (None, "model")),
)

# Non-linear leaves: path regex -> spec (without stacked prefix).
_LEAF_RULES: Sequence[Tuple[str, Tuple]] = (
    (r"(^|/)embed/table$", ("model", None)),
    (r"(^|/)pos/table$", (None, None)),
    (r"experts/(wi|wg|wo)/kernel$", ("model", None, None)),
    (r"experts/(wi|wg|wo)/(u|v|u2|v2)$", ("model", None, None)),
    (r"mamba/conv/w$", (None, "model")),
    (r"mamba/conv/b$", ("model",)),
    (r"mamba/dt_proj/kernel$", (None, "model")),
    (r"mamba/dt_proj/bias$", ("model",)),
    (r"mamba/a_log$", ("model", None)),
    (r"mamba/d_skip$", ("model",)),
    (r"rwkv_t/bonus$", ("model", None)),
    (r"rwkv_t/(ln_scale|ln_bias)$", ("model",)),
)


def _match_linear(path: str):
    for pat, axes in _LINEAR_RULES:
        if re.search(pat, path):
            return axes
    return None


def _match_leaf(path: str, ndim: int):
    for pat, spec in _LEAF_RULES:
        if re.search(pat, path):
            return spec
    return None


def param_pspec(path: Tuple[str, ...], leaf, fsdp_axes=None) -> P:
    """PartitionSpec for one param leaf given its pytree path.

    ``fsdp_axes``: additionally shard each 2D+ weight over the DP axes on
    its first TP-free dim (ZeRO-3/FSDP storage; XLA inserts per-layer
    all-gather at use and reduce-scatter on grads).  Required to fit the
    671B-class archs (EXPERIMENTS.md §Dry-run memory table).
    """
    ndim = len(leaf.shape)
    joined = "/".join(path)
    parent = "/".join(path[:-1])
    key = path[-1]

    def with_fsdp(entries):
        if not fsdp_axes or ndim < 2:
            return P(*entries)
        entries = list(entries)
        dp = tuple(fsdp_axes) if len(fsdp_axes) > 1 else fsdp_axes[0]
        # Largest free dim gets the DP axes (skip tiny dims).
        free = [
            i for i, e in enumerate(entries)
            if e is None and leaf.shape[i] >= 128
        ]
        if free:
            target = max(free, key=lambda i: leaf.shape[i])
            entries[target] = dp
        return P(*entries)

    spec = _match_leaf(joined, ndim)
    if spec is not None:
        pad = ndim - len(spec)
        return with_fsdp([None] * pad + list(spec))

    if key in ("kernel", "u", "v", "u2", "v2", "table"):
        axes = _match_linear(parent)
        if axes is None:
            return P()  # replicate unknown linears
        in_ax, out_ax = axes
        if key == "kernel":
            mat = (in_ax, out_ax)
        elif key in ("u", "u2"):
            # Factored params: shard u on its INPUT dim and v on its OUTPUT
            # dim regardless of the dense kernel's orientation — inheriting
            # the dense boundary would leave u fully replicated for every
            # column-parallel layer (measured: 2.7x the dense per-device
            # bytes at ratio 0.3!).  Cost: one rank-width psum per factored
            # column-parallel matmul — k/d_model of the dense TP collective
            # (§Perf pair C, iteration C1).
            mat = (None, None) if (in_ax is None and out_ax is None) else ("model", None)
        else:  # v / v2
            mat = (None, None) if (in_ax is None and out_ax is None) else (None, "model")
        pad = ndim - 2
        return with_fsdp([None] * pad + list(mat))

    return P()  # norms, biases, scalars: replicated


def tree_paths(tree, prefix=()) -> Dict[Tuple[str, ...], Any]:
    out = {}
    if isinstance(tree, Mapping):
        for k, v in tree.items():
            out.update(tree_paths(v, prefix + (str(k),)))
    else:
        out[prefix] = tree
    return out


def param_shardings(params_shape, mesh: Mesh, fsdp_axes=None):
    """Pytree of NamedSharding matching a params (shape) pytree."""

    def walk(tree, prefix=()):
        if isinstance(tree, Mapping):
            return {k: walk(v, prefix + (str(k),)) for k, v in tree.items()}
        return NamedSharding(mesh, param_pspec(prefix, tree, fsdp_axes))

    return walk(params_shape)


def param_pspecs(params_shape, fsdp_axes=None):
    """Pytree of raw PartitionSpec (mesh-independent)."""

    def walk(tree, prefix=()):
        if isinstance(tree, Mapping):
            return {k: walk(v, prefix + (str(k),)) for k, v in tree.items()}
        return param_pspec(prefix, tree, fsdp_axes)

    return walk(params_shape)


def moe_shard_specs(moe_params_shape) -> Any:
    """in_specs for the MoE shard_map: experts sharded on 'model', shared
    experts TP-sliced, router replicated."""

    def walk(tree, prefix=()):
        if isinstance(tree, Mapping):
            return {k: walk(v, prefix + (str(k),)) for k, v in tree.items()}
        return param_pspec(prefix, tree)

    return walk(moe_params_shape)
