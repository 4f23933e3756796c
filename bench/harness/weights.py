"""Random NSVD-factored weights, made on the device in one jitted call.

The program's rank plan (``core.build_plan``, method and ratio from the
configuration file, the ``split_rank`` k1/k2 split) fixes every factor's
shape; the dense targets are never materialized and no host decomposition
runs: the compression ratio sets the speed, the factor values do not.
Each leaf keeps the dtype the model's own ``init`` gives it (the router of
a mixture of experts stays float32).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


def key_words(seed: int, salt: int, n: int) -> np.ndarray:
    """``n`` uint32 words of key data from any non-negative integer seed
    (wider than 32 bits too), through numpy's SeedSequence."""
    return np.random.SeedSequence([int(seed), int(salt)]).generate_state(n)


def jax_key(seed: int, salt: int = 0):
    """A JAX key from any non-negative integer seed.  The "rbg" generator
    draws with the chip's own random-bit generator, far faster than
    threefry for the billions of weights a configuration needs."""
    return jax.random.wrap_key_data(
        jnp.asarray(key_words(seed, salt, 4), jnp.uint32), impl="rbg")


class FactoredRow(NamedTuple):
    """One factored linear of the plan.  ``experts``: a stack of routed
    experts, whose last ``stacked`` dim counts the experts held."""
    path: tuple
    in_dim: int
    out_dim: int
    k1: int
    k2: int
    stacked: tuple
    experts: bool = False


def rank_plan(model, compression: dict):
    """{target path: (k1, k2)} of the program's NSVD plan, plus the plan."""
    from repro.core import CompressionConfig, build_plan
    from repro.core.nsvd import split_rank

    plan = build_plan(model.compressible_targets(), CompressionConfig(
        method=compression["method"], ratio=compression["ratio"],
        k1_frac=compression["k1_frac"], dtype=model.cfg.dtype))
    return {t.path: split_rank(plan.rank_of(t), plan.config.k1_frac)
            for t in plan.targets}, plan


def param_shapes(model, compression: dict):
    """ShapeDtypeStructs of the factored params tree (nothing allocated)."""
    return jax.eval_shape(lambda: make_params(model, compression, None))


def make_params(model, compression: dict, key):
    """The factored params tree, drawn from ``key`` (None: shapes only,
    under ``jax.eval_shape``)."""
    ranks, plan = rank_plan(model, compression)
    targets = {t.path: t for t in plan.targets}
    dense = jax.eval_shape(model.init, jax.random.key(0))
    counter = iter(range(1 << 30))

    def normal(shape, std, dt):
        if key is None:
            return jnp.zeros(shape, dt)
        k = jax.random.fold_in(key, next(counter))
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dt)

    def build(tree, path=()):
        if path in targets:
            t = targets[path]
            k1, k2 = ranks[path]
            lead = tuple(t.stacked)
            rank = k1 + k2
            dt = tree["kernel"].dtype
            return {
                "u": normal(lead + (t.in_dim, k1), t.in_dim ** -0.5, dt),
                "v": normal(lead + (k1, t.out_dim), rank ** -0.5, dt),
                "u2": normal(lead + (t.in_dim, k2), t.in_dim ** -0.5, dt),
                "v2": normal(lead + (k2, t.out_dim), rank ** -0.5, dt),
            }
        if isinstance(tree, dict):
            return {k: build(v, path + (k,)) for k, v in tree.items()}
        name = path[-1]
        if name == "scale":
            return jnp.ones(tree.shape, tree.dtype)
        if name == "bias":
            return jnp.zeros(tree.shape, tree.dtype)
        if name == "table":
            return normal(tree.shape, 0.02, tree.dtype)
        if name == "kernel":
            return normal(tree.shape, tree.shape[-2] ** -0.5, tree.dtype)
        raise ValueError(f"no rule for param {'/'.join(path)}")

    return build(dense)


def build_params(model, compression: dict, seed: int, shardings=None):
    """Factored params in one jitted call: on the default device, or, with
    ``shardings`` (a tree of NamedShardings over a mesh), each leaf drawn
    straight into its sharding (``sharded_draw``)."""
    if shardings is None:
        return jax.jit(lambda k: make_params(model, compression, k))(
            jax_key(seed, 1))
    return sharded_draw(model, compression, shardings)(
        key_words(seed, 1, 2))


def sharded_draw(model, compression: dict, shardings):
    """The jitted draw of the params into ``shardings``, from two uint32
    words of key data.  XLA's SPMD partitioner does not split the chip's
    random-bit generator: under "rbg" every device would draw a sharded
    leaf whole and keep its slice.  Threefry in its partitionable form
    draws on each device only that device's slice."""
    def draw(words):
        with jax.threefry_partitionable(True):
            key = jax.random.wrap_key_data(words, impl="threefry2x32")
            return make_params(model, compression, key)

    return jax.jit(draw, out_shardings=shardings)


def factored_rows(model, compression: dict) -> list:
    """A FactoredRow of every factored linear; the stacks of routed
    experts (``experts.*``, stacked layers x experts) are marked."""
    ranks, plan = rank_plan(model, compression)
    return [FactoredRow(t.path, t.in_dim, t.out_dim, *ranks[t.path],
                        tuple(t.stacked), "experts" in t.path)
            for t in plan.targets]


def param_bytes(tree) -> int:
    return int(sum(np.prod(x.shape) * jnp.dtype(x.dtype).itemsize
                   for x in jax.tree.leaves(tree)))
