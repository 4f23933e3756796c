"""Random NSVD-factored weights, made on the device in one jitted call.

The program's rank plan (``core.build_plan``, method and ratio from the
configuration file, the ``split_rank`` k1/k2 split) fixes every factor's
shape; the dense targets are never materialized and no host decomposition
runs: the compression ratio sets the speed, the factor values do not.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def jax_key(seed: int, salt: int = 0):
    """A JAX key from any non-negative integer seed (wider than 32 bits
    too), through numpy's SeedSequence.  The "rbg" generator draws with the
    chip's own random-bit generator, far faster than threefry for the
    billions of weights a configuration needs."""
    words = np.random.SeedSequence([int(seed), int(salt)]).generate_state(4)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="rbg")


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
    dt = jnp.dtype(model.cfg.dtype)
    counter = iter(range(1 << 30))

    def normal(shape, std):
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
            return {
                "u": normal(lead + (t.in_dim, k1), t.in_dim ** -0.5),
                "v": normal(lead + (k1, t.out_dim), rank ** -0.5),
                "u2": normal(lead + (t.in_dim, k2), t.in_dim ** -0.5),
                "v2": normal(lead + (k2, t.out_dim), rank ** -0.5),
            }
        if isinstance(tree, dict):
            return {k: build(v, path + (k,)) for k, v in tree.items()}
        name = path[-1]
        if name == "scale":
            return jnp.ones(tree.shape, dt)
        if name == "bias":
            return jnp.zeros(tree.shape, dt)
        if name == "table":
            return normal(tree.shape, 0.02)
        if name == "kernel":
            return normal(tree.shape, tree.shape[-2] ** -0.5)
        raise ValueError(f"no rule for param {'/'.join(path)}")

    return build(dense)


def build_params(model, compression: dict, seed: int):
    """Factored params on the default device, in one jitted call."""
    return jax.jit(lambda k: make_params(model, compression, k))(
        jax_key(seed, 1))


def factored_rows(model, compression: dict) -> list:
    """(path, in_dim, out_dim, k1, k2, stacked) of every factored linear."""
    ranks, plan = rank_plan(model, compression)
    return [(t.path, t.in_dim, t.out_dim, *ranks[t.path], tuple(t.stacked))
            for t in plan.targets]


def param_bytes(tree) -> int:
    return int(sum(np.prod(x.shape) * jnp.dtype(x.dtype).itemsize
                   for x in jax.tree.leaves(tree)))
