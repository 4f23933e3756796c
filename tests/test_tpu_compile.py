"""Compile-only tests of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a *described* v5e:2x2 topology and raises whatever Mosaic would refuse
on the chip (unsupported vector layouts, tiles not aligned to the (8, 128)
tiling).  Nothing runs, so these tests say nothing about results or speed.

The topology is described inside a module-scoped fixture, never while the
module is imported: only one process may load the TPU library at a time,
and under several pytest workers the other workers must still collect the
same tests (they skip from the fixture if the library is held).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs import get_config
from repro.core import CompressionConfig, build_plan
from repro.core.nsvd import split_rank
from repro.models import build_model

MISTRAL = get_config("mistral-7b")


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache off meanwhile.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _decode_args(batch, hq, hkv, dtype, sharding, num_blocks=512,
                 blocks_per_row=32, kv_dtype=None):
    """q, pools, block tables, lengths (+ int8 dequant scale pools)."""
    hd = MISTRAL.head_dim
    pool = (num_blocks, 16, hkv, hd)
    kv = kv_dtype or dtype
    args = (_sds((batch, hq, hd), dtype, sharding),
            _sds(pool, kv, sharding), _sds(pool, kv, sharding),
            _sds((batch, blocks_per_row), jnp.int32, sharding),
            _sds((batch,), jnp.int32, sharding))
    if kv == jnp.int8:
        args += (_sds(pool[:3], jnp.float32, sharding),) * 2
    return args


class TestPagedAttention:
    """Mistral-7B decode shapes: 32 query heads over 8 KV heads of 128,
    16-token pages, 32 pages (512 tokens) per row."""

    @pytest.mark.parametrize("batch,dtype,kv_dtype", [
        (8, jnp.bfloat16, None),     # the served batch
        (5, jnp.bfloat16, None),     # ragged last pack
        (8, jnp.float32, None),
        (8, jnp.bfloat16, jnp.int8),  # int8 pools + dequant scales
    ], ids=["bf16", "bf16-ragged", "f32", "int8"])
    def test_compiles_for_v5e(self, one_chip, batch, dtype, kv_dtype):
        from repro.kernels.paged_attention.paged_attention import (
            paged_attention,
        )

        args = _decode_args(batch, MISTRAL.num_heads, MISTRAL.num_kv_heads,
                            dtype, one_chip, kv_dtype=kv_dtype)
        compiled = jax.jit(paged_attention).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()

    def test_compiles_per_device_on_2x2_mesh(self, topo):
        """Under a DP x TP mesh the ops wrapper runs the kernel per device
        (Mosaic kernels cannot be partitioned automatically)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.kernels.paged_attention import ops
        from repro.parallel.sharding import make_parallelism, tracing_with

        mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
        par = make_parallelism(mesh)
        rows, pools = NamedSharding(mesh, P("data")), NamedSharding(
            mesh, P("data", None, None, None))
        q, kp, vp, bt, ln = _decode_args(
            8, MISTRAL.num_heads, MISTRAL.num_kv_heads, jnp.bfloat16, rows)
        kp, vp = (_sds(a.shape, a.dtype, pools) for a in (kp, vp))

        def step(q, kp, vp, bt, ln):
            with tracing_with(par):
                return ops.paged_attention(q, kp, vp, bt, ln, use_kernel=True)

        compiled = jax.jit(step).lower(q, kp, vp, bt, ln).compile()
        assert "tpu_custom_call" in compiled.as_text()


def _planned(target: str, ratio: float):
    """(in, out, k1, k2) the NSVD planner gives ``target`` of Mistral-7B."""
    model = build_model(dataclasses.replace(MISTRAL, num_layers=1))
    plan = build_plan(model.compressible_targets(),
                      CompressionConfig(method="nsvd1", ratio=ratio))
    spec = next(s for s in plan.targets if s.name.endswith(target))
    k1, k2 = split_rank(plan.rank_of(spec), plan.config.k1_frac)
    return spec.in_dim, spec.out_dim, k1, k2


class TestNestedLowrank:
    @pytest.mark.parametrize("target,ratio", [
        ("attn/wq", 0.2),   # q/o: 4096 -> 4096
        ("attn/wk", 0.2),   # k/v: 4096 -> 1024
        ("mlp/wi", 0.2),    # gate/up: 4096 -> 14336
        ("mlp/wo", 0.9),    # down: 14336 -> 4096 (0.2 compiles in ~10 s)
    ])
    def test_compiles_for_v5e_at_planned_ranks(self, one_chip, target,
                                                ratio):
        from repro.kernels.nested_lowrank.nested_lowrank import (
            nested_lowrank_matmul,
        )

        k_in, n, k1, k2 = _planned(target, ratio)
        n_pad = -(-n // 256) * 256
        bf16 = jnp.bfloat16
        args = (_sds((8, k_in), bf16, one_chip),
                _sds((k_in, k1), bf16, one_chip),
                _sds((k1, n_pad), bf16, one_chip),
                _sds((k_in, k2), bf16, one_chip),
                _sds((k2, n_pad), bf16, one_chip))
        compiled = jax.jit(nested_lowrank_matmul).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()
