"""Pallas kernel validation: interpret-mode vs pure-jnp oracle, with
shape/dtype sweeps (the kernels target TPU; interpret=True executes the
kernel body on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.gram.ops import gram_accumulate
from repro.kernels.gram.ref import gram_accumulate_ref
from repro.kernels.nested_lowrank.ops import nested_lowrank_matmul
from repro.kernels.nested_lowrank.ref import nested_lowrank_matmul_ref
from repro.kernels.rwkv6.ops import rwkv6_attention
from repro.kernels.rwkv6.ref import rwkv6_scan_ref


def _tol(dtype):
    # bf16: the kernel accumulates in fp32 while the oracle round-trips
    # intermediates through bf16, so small divergence is expected (and the
    # kernel is the MORE accurate side).
    return dict(rtol=6e-2, atol=6e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


class TestNestedLowRank:
    @pytest.mark.parametrize("m,kin,k1,k2,n", [
        (8, 64, 16, 4, 128),
        (16, 128, 32, 8, 256),
        (4, 96, 24, 8, 192),     # non-128-aligned K
        (32, 256, 128, 16, 512), # multiple output tiles
        (8, 64, 16, 4, 100),     # N not divisible by block -> padded
        (8, 64, 16, 4, 320),     # N > block and not divisible -> padded tiles
        (4, 64, 16, 4, 130),     # N barely over one block
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_oracle(self, m, kin, k1, k2, n, dtype):
        rng = np.random.default_rng(0)
        mk = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.3, dtype)
        x, u, v = mk(m, kin), mk(kin, k1), mk(k1, n)
        u2, v2 = mk(kin, k2), mk(k2, n)
        got = nested_lowrank_matmul(x, u, v, u2, v2, block_n=128, interpret=True)
        want = nested_lowrank_matmul_ref(x, u, v, u2, v2)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype)
        )

    def test_linear_apply_routes_nested_through_ops(self):
        """linear_apply's default dispatch (ops.py: kernel on TPU, oracle on
        CPU) must agree with the explicit jnp path for nested params."""
        from repro.core.lowrank import linear_apply

        rng = np.random.default_rng(7)
        params = {
            "u": jnp.asarray(rng.standard_normal((64, 16)), jnp.float32),
            "v": jnp.asarray(rng.standard_normal((16, 96)), jnp.float32),
            "u2": jnp.asarray(rng.standard_normal((64, 4)), jnp.float32),
            "v2": jnp.asarray(rng.standard_normal((4, 96)), jnp.float32),
        }
        x = jnp.asarray(rng.standard_normal((3, 5, 64)), jnp.float32)
        auto = linear_apply(params, x)  # default: route through ops
        plain = linear_apply(params, x, use_kernel=False)
        np.testing.assert_allclose(np.asarray(auto), np.asarray(plain),
                                   rtol=1e-6, atol=1e-6)

    def test_batched_leading_dims(self):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.standard_normal((2, 3, 64)), jnp.float32)
        u = jnp.asarray(rng.standard_normal((64, 8)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((8, 128)), jnp.float32)
        u2 = jnp.asarray(rng.standard_normal((64, 4)), jnp.float32)
        v2 = jnp.asarray(rng.standard_normal((4, 128)), jnp.float32)
        got = nested_lowrank_matmul(x, u, v, u2, v2, interpret=True)
        want = nested_lowrank_matmul_ref(x, u, v, u2, v2)
        assert got.shape == (2, 3, 128)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


class TestGram:
    @pytest.mark.parametrize("rows,n", [
        (512, 128), (1024, 256), (300, 96), (64, 64),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_oracle(self, rows, n, dtype):
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.standard_normal((rows, n)) * 0.5, dtype)
        got = gram_accumulate(x, block_n=64, block_t=128, interpret=True)
        want = gram_accumulate_ref(x)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want),
            rtol=3e-2 if dtype == jnp.bfloat16 else 1e-4,
            atol=3e-1 if dtype == jnp.bfloat16 else 1e-3,
        )

    def test_gram_is_symmetric_psd(self):
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.standard_normal((256, 64)), jnp.float32)
        g = np.asarray(gram_accumulate(x, block_n=64, block_t=64, interpret=True))
        np.testing.assert_allclose(g, g.T, rtol=1e-6)
        evals = np.linalg.eigvalsh(g)
        assert evals.min() > -1e-3


class TestRWKV6:
    @pytest.mark.parametrize("bh,t,k,chunk", [
        (2, 32, 16, 8),
        (4, 64, 32, 16),
        (1, 48, 64, 16),
        (2, 40, 16, 16),   # T not divisible by chunk -> padded
    ])
    def test_matches_scan_oracle(self, bh, t, k, chunk):
        rng = np.random.default_rng(4)
        r = jnp.asarray(rng.standard_normal((bh, t, k)) * 0.5, jnp.float32)
        kk = jnp.asarray(rng.standard_normal((bh, t, k)) * 0.5, jnp.float32)
        v = jnp.asarray(rng.standard_normal((bh, t, k)) * 0.5, jnp.float32)
        # decays in (0, 1) incl. strong decay (the overflow-prone regime)
        w = jnp.asarray(rng.uniform(0.01, 0.999, (bh, t, k)), jnp.float32)
        u = jnp.asarray(rng.standard_normal((bh, k)) * 0.5, jnp.float32)
        got = rwkv6_attention(r, kk, v, w, u, chunk=chunk, interpret=True)
        want = rwkv6_scan_ref(r, kk, v, w, u)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
        )

    def test_extreme_decay_no_overflow(self):
        """Strong decay (w -> 0) is where naive chunk algebra overflows."""
        rng = np.random.default_rng(5)
        bh, t, k = 2, 32, 16
        r = jnp.asarray(rng.standard_normal((bh, t, k)), jnp.float32)
        kk = jnp.asarray(rng.standard_normal((bh, t, k)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((bh, t, k)), jnp.float32)
        w = jnp.full((bh, t, k), 1e-6, jnp.float32)
        u = jnp.zeros((bh, k), jnp.float32)
        got = rwkv6_attention(r, kk, v, w, u, chunk=8, interpret=True)
        want = rwkv6_scan_ref(r, kk, v, w, u)
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-3, atol=1e-3)

    def test_model_layer_uses_same_math(self):
        """The rwkv6 model layer's scan and the kernel oracle agree on a
        round-trip through the model's tensor layout."""
        from repro.configs import get_config
        from repro.models import build_model

        cfg = get_config("rwkv6-1.6b").reduced()
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (2, 12), 0, cfg.vocab_size)
        logits, _, _ = model.apply(params, tokens, mode="train")
        assert jnp.isfinite(logits).all()


@pytest.mark.parametrize("rows", [64, 192])
def test_gram_kernel_vs_calibration_update(rows):
    """Kernel output feeds the same Gram the calibration runner computes."""
    from repro.calib.gram import gram_update

    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((rows, 32)), jnp.float32)
    g_kernel = gram_accumulate(x, block_n=32, block_t=64, interpret=True)
    g_runner, _, _ = gram_update(x)
    np.testing.assert_allclose(np.asarray(g_kernel), np.asarray(g_runner), rtol=1e-4, atol=1e-4)


def test_count_routes_tallies_each_traced_call_site():
    """The tally a caller opens records which path every call site took:
    the XLA oracle by default off-TPU, the kernel when forced."""
    from repro.kernels.routes import count_routes

    rng = np.random.default_rng(0)
    x, u, v, u2, v2 = (jnp.asarray(rng.standard_normal(s), jnp.float32)
                       for s in ((8, 32), (32, 8), (8, 128), (32, 4),
                                 (4, 128)))
    with count_routes() as tally:
        nested_lowrank_matmul(x, u, v, u2, v2)
        nested_lowrank_matmul(x, u, v, u2, v2, interpret=True)
    assert tally == {("nested_lowrank", "xla"): 1,
                     ("nested_lowrank", "pallas"): 1}
    nested_lowrank_matmul(x, u, v, u2, v2)  # outside: nothing to tally
    assert sum(tally.values()) == 2
