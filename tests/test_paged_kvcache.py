"""Paged KV-cache subsystem tests: block allocator, Pallas paged-attention
kernel vs jnp oracle, paged-vs-dense decode equivalence (incl. int8 KV
quant and chunked prefill across block/chunk boundaries), block-exhaustion
admission backpressure, block reuse after completion, buffer donation on
the jit roots, device-side EOS early exit, cache_layout gating, and the
MoE expert-matmul routing through the nested-lowrank kernel ops."""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_models import small_lm
from repro.models import build_model, cache_layout
from repro.serving.engine import ServingEngine
from repro.serving.kvcache import BlockAllocator, PagedKVCache

VOCAB = 256


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = small_lm(name="tiny-paged", vocab_size=VOCAB, num_layers=2,
                   d_model=64, d_ff=96, num_heads=4)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return model, params


def _solo(model, params, prompt, max_new, max_len=64, **kw):
    eng = ServingEngine(model, params, max_batch=1, max_len=max_len, **kw)
    uid = eng.submit(prompt, max_new_tokens=max_new)
    return eng.run()[uid]


# ---------------------------------------------------------------- allocator


class TestBlockAllocator:
    def test_alloc_free_reuse(self):
        a = BlockAllocator(8)
        ids = a.alloc("r0", 3)
        assert ids == [0, 1, 2] and a.in_use() == 3
        assert a.alloc("r1", 5) == [3, 4, 5, 6, 7]
        assert a.alloc("r2", 1) is None and a.in_use() == 8  # no state change
        assert sorted(a.free("r0")) == [0, 1, 2]
        assert a.alloc("r2", 2) == [0, 1]  # lowest ids reused first
        assert a.peak_in_use == 8

    def test_incremental_alloc_appends(self):
        a = BlockAllocator(4)
        a.alloc("r", 1)
        a.alloc("r", 2)
        assert a.owned_by("r") == [0, 1, 2]
        assert a.free("r") == [0, 1, 2] and a.in_use() == 0

    def test_defrag_compacts_live_blocks(self):
        a = BlockAllocator(8)
        a.alloc("A", 2)  # [0, 1]
        a.alloc("B", 2)  # [2, 3]
        a.alloc("C", 2)  # [4, 5]
        a.free("B")
        moves = a.defrag()
        assert moves == {4: 2, 5: 3}
        assert a.owned_by("C") == [2, 3]
        assert a.owned_by("A") == [0, 1]
        assert a.free_blocks() == 4
        assert a.defrag() == {}  # already compact


# ------------------------------------------------------------------- kernel


class TestPagedAttentionKernel:
    @pytest.mark.parametrize("b,hq,hkv,hd,bs,lens", [
        (2, 4, 4, 32, 16, (5, 30)),      # MHA (G=1)
        (3, 8, 2, 64, 16, (1, 16, 47)),  # GQA, block-boundary lengths
        (1, 4, 1, 32, 8, (17,)),         # single kv head, odd length
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_oracle(self, b, hq, hkv, hd, bs, lens, dtype):
        from repro.kernels.paged_attention.ops import paged_attention
        from repro.kernels.paged_attention.ref import paged_attention_ref

        rng = np.random.default_rng(0)
        n, m = 12, 4
        q = jnp.asarray(rng.standard_normal((b, hq, hd)) * 0.3, dtype)
        kp = jnp.asarray(rng.standard_normal((n, bs, hkv, hd)) * 0.3, dtype)
        vp = jnp.asarray(rng.standard_normal((n, bs, hkv, hd)) * 0.3, dtype)
        bt = np.full((b, m), -1, np.int32)
        blocks = iter(rng.permutation(n))
        for r, ln in enumerate(lens):
            for j in range(-(-ln // bs)):
                bt[r, j] = next(blocks)
        bt, ln = jnp.asarray(bt), jnp.asarray(np.asarray(lens, np.int32))
        got = paged_attention(q, kp, vp, bt, ln, interpret=True)
        want = paged_attention_ref(q, kp, vp, bt, ln)
        tol = 5e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=tol, atol=tol,
        )

    def test_int8_quantized_pools_match_oracle(self):
        from repro.kernels.paged_attention.ops import paged_attention
        from repro.kernels.paged_attention.ref import paged_attention_ref

        rng = np.random.default_rng(1)
        b, hq, hkv, hd, bs, n, m = 2, 8, 4, 32, 16, 8, 3
        q = jnp.asarray(rng.standard_normal((b, hq, hd)), jnp.float32)
        kp = jnp.asarray(rng.integers(-127, 127, (n, bs, hkv, hd)), jnp.int8)
        vp = jnp.asarray(rng.integers(-127, 127, (n, bs, hkv, hd)), jnp.int8)
        ks = jnp.asarray(rng.uniform(0.01, 0.1, (n, bs, hkv)), jnp.float32)
        vs = jnp.asarray(rng.uniform(0.01, 0.1, (n, bs, hkv)), jnp.float32)
        bt = jnp.asarray([[0, 1, 2], [3, 4, -1]], jnp.int32)
        ln = jnp.asarray([40, 20], jnp.int32)
        got = paged_attention(q, kp, vp, bt, ln, ks, vs, interpret=True)
        want = paged_attention_ref(q, kp, vp, bt, ln, ks, vs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_masked_rows_write_nowhere(self):
        """A row whose block-table entries are -1 (inactive/freed, or an
        admission pad row) must not write a single pool element.  Guards a
        subtle jnp footgun: .at[...].set(mode="drop") normalizes NEGATIVE
        indices before dropping, so a -1 flat sentinel would silently
        clobber the last slot of the highest pool block — which can belong
        to a live request."""
        from repro.models.attention import _paged_decode_attend

        h, hd, bs, n = 2, 16, 8, 3
        cache = {"k": jnp.zeros((n, bs, h, hd)), "v": jnp.zeros((n, bs, h, hd))}
        ones = jnp.ones((1, 1, h, hd))
        bt = jnp.full((1, 2), -1, jnp.int32)
        for clen in (0, 7, bs * n - 1, bs * n + 5):  # incl. wrap-prone spots
            _, new_cache = _paged_decode_attend(
                ones, ones, ones, cache, jnp.asarray([clen], jnp.int32),
                bt, scale=0.25,
            )
            assert (np.asarray(new_cache["k"]) == 0).all(), clen
            assert (np.asarray(new_cache["v"]) == 0).all(), clen

    @pytest.mark.parametrize("b,hq,hkv,rpp", [
        (3, 4, 4, 2),   # G=1, ragged last pack (3 rows into packs of 2)
        (5, 8, 2, 4),   # G=4, ragged (5 rows into packs of 4)
        (7, 4, 2, 8),   # G=2, single partial pack wider than the batch
        (4, 4, 1, 1),   # packing disabled == per-row schedule
    ])
    def test_packed_rows_match_oracle(self, b, hq, hkv, rpp):
        """Row-packed grid steps (including a ragged final pack) must be
        invisible in the result: the packed score tile's cross-row
        quadrants are masked, so any rows_per_pack equals the per-row
        oracle."""
        from repro.kernels.paged_attention.ops import paged_attention
        from repro.kernels.paged_attention.ref import (
            paged_attention_packed_ref,
            paged_attention_ref,
        )

        rng = np.random.default_rng(20)
        hd, bs, n, m = 32, 8, 16, 4
        lens = rng.integers(1, m * bs + 1, size=b)
        q = jnp.asarray(rng.standard_normal((b, hq, hd)) * 0.3, jnp.float32)
        kp = jnp.asarray(rng.standard_normal((n, bs, hkv, hd)) * 0.3,
                         jnp.float32)
        vp = jnp.asarray(rng.standard_normal((n, bs, hkv, hd)) * 0.3,
                         jnp.float32)
        bt = np.full((b, m), -1, np.int32)
        blocks = iter(rng.permutation(n))
        for r, ln in enumerate(lens):
            for j in range(-(-int(ln) // bs)):
                bt[r, j] = next(blocks)
        bt = jnp.asarray(bt)
        ln = jnp.asarray(lens.astype(np.int32))
        want = paged_attention_ref(q, kp, vp, bt, ln)
        got = paged_attention(q, kp, vp, bt, ln, interpret=True,
                              rows_per_pack=rpp)
        packed = paged_attention_packed_ref(q, kp, vp, bt, ln,
                                            rows_per_pack=rpp)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(packed), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_packed_page_edge_lengths(self):
        """Per-row lengths landing exactly on page edges (bs-1, bs, bs+1,
        full table) inside ONE pack: the shared page loop runs to the
        longest row and the per-row length columns mask the rest."""
        from repro.kernels.paged_attention.ops import paged_attention
        from repro.kernels.paged_attention.ref import paged_attention_ref

        rng = np.random.default_rng(21)
        bs, hkv, g, hd, m = 8, 2, 2, 32, 4
        lens = np.asarray([bs - 1, bs, bs + 1, m * bs], np.int32)
        b, hq, n = len(lens), hkv * g, 20
        q = jnp.asarray(rng.standard_normal((b, hq, hd)) * 0.3, jnp.float32)
        kp = jnp.asarray(rng.standard_normal((n, bs, hkv, hd)) * 0.3,
                         jnp.float32)
        vp = jnp.asarray(rng.standard_normal((n, bs, hkv, hd)) * 0.3,
                         jnp.float32)
        bt = np.full((b, m), -1, np.int32)
        blocks = iter(rng.permutation(n))
        for r, ln in enumerate(lens):
            for j in range(-(-int(ln) // bs)):
                bt[r, j] = next(blocks)
        got = paged_attention(q, kp, vp, jnp.asarray(bt), jnp.asarray(lens),
                              interpret=True, rows_per_pack=4)
        want = paged_attention_ref(q, kp, vp, jnp.asarray(bt),
                                   jnp.asarray(lens))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_packed_int8_dequant_in_tile(self):
        """int8 pools dequantize inside the packed tile: each packed row's
        pages carry their own scales, so cross-row packing must not mix
        them (ragged 3-row pack of 2 exercises the pad row too)."""
        from repro.kernels.paged_attention.ops import paged_attention
        from repro.kernels.paged_attention.ref import (
            paged_attention_packed_ref,
            paged_attention_ref,
        )

        rng = np.random.default_rng(22)
        b, hq, hkv, hd, bs, n, m = 3, 8, 4, 32, 16, 8, 3
        q = jnp.asarray(rng.standard_normal((b, hq, hd)), jnp.float32)
        kp = jnp.asarray(rng.integers(-127, 127, (n, bs, hkv, hd)), jnp.int8)
        vp = jnp.asarray(rng.integers(-127, 127, (n, bs, hkv, hd)), jnp.int8)
        ks = jnp.asarray(rng.uniform(0.01, 0.1, (n, bs, hkv)), jnp.float32)
        vs = jnp.asarray(rng.uniform(0.01, 0.1, (n, bs, hkv)), jnp.float32)
        bt = jnp.asarray([[0, 1, 2], [3, 4, -1], [5, -1, -1]], jnp.int32)
        ln = jnp.asarray([40, 20, 9], jnp.int32)
        want = paged_attention_ref(q, kp, vp, bt, ln, ks, vs)
        got = paged_attention(q, kp, vp, bt, ln, ks, vs, interpret=True,
                              rows_per_pack=2)
        packed = paged_attention_packed_ref(q, kp, vp, bt, ln, ks, vs,
                                            rows_per_pack=2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(packed), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_default_rows_per_pack_fills_sublanes(self):
        from repro.kernels.paged_attention.ops import default_rows_per_pack

        assert default_rows_per_pack(16, 1) == 8   # G=1 -> 8 rows
        assert default_rows_per_pack(16, 2) == 4
        assert default_rows_per_pack(16, 4) == 2
        assert default_rows_per_pack(16, 8) == 1
        assert default_rows_per_pack(1, 1) == 1    # never pad past batch
        assert default_rows_per_pack(3, 1) == 3

    def test_cpu_dispatch_uses_oracle(self):
        """On non-TPU backends the ops wrapper must never touch the kernel."""
        from repro.kernels.paged_attention import ops

        rng = np.random.default_rng(2)
        q = jnp.asarray(rng.standard_normal((1, 4, 32)), jnp.float32)
        kp = jnp.asarray(rng.standard_normal((2, 8, 4, 32)), jnp.float32)
        bt = jnp.asarray([[0, 1]], jnp.int32)
        ln = jnp.asarray([9], jnp.int32)
        with mock.patch.object(ops, "_kernel_call",
                               side_effect=AssertionError("kernel on CPU")):
            out = ops.paged_attention(q, kp, kp, bt, ln)
        assert out.shape == (1, 4, 32)


# ----------------------------------------------------- paged decode parity


class TestPagedDenseEquivalence:
    def test_greedy_identical_across_block_boundaries(self, tiny_lm):
        """Prompt lengths straddling block (16) and chunk boundaries must
        produce exactly the dense-slab greedy tokens."""
        model, params = tiny_lm
        rng = np.random.default_rng(3)
        for plen in (1, 15, 16, 17, 31, 33):
            p = rng.integers(2, 200, size=plen)
            dense = _solo(model, params, p, 8, paged=False)
            paged = _solo(model, params, p, 8, paged=True, prefill_chunk=16)
            assert dense == paged, f"plen={plen}"

    def test_batched_greedy_identical(self, tiny_lm):
        model, params = tiny_lm
        rng = np.random.default_rng(4)
        prompts = [rng.integers(2, 200, size=n) for n in (5, 18, 9, 33)]

        def run(paged):
            eng = ServingEngine(model, params, max_batch=2, max_len=64,
                                paged=paged)
            uids = [eng.submit(p, max_new_tokens=6) for p in prompts]
            out = eng.run()
            return [out[u] for u in uids]

        assert run(True) == run(False)

    def test_int8_kv_quant_identical(self, tiny_lm):
        """Paged pools quantize/dequantize the same per-position vectors as
        the dense slab, so DECODE-phase attention inputs are bit-identical.
        Prefill differs slightly by design (chunked prefill attends the
        cache-consistent dequantized view; dense prefill attends raw fp and
        quantizes only for storage), so token equality here relies on this
        fixed model's logit margins exceeding the int8 noise — which the
        deterministic fixture pins."""
        model, params = tiny_lm
        rng = np.random.default_rng(5)
        p = rng.integers(2, 200, size=19)
        dense = _solo(model, params, p, 6, paged=False, kv_quant=True)
        paged = _solo(model, params, p, 6, paged=True, kv_quant=True)
        assert dense == paged

    def test_temperature_sampling_identical(self, tiny_lm):
        """Per-slot PRNG keys are layout-independent state: sampled paths
        must match between cache layouts, not just greedy ones."""
        model, params = tiny_lm
        rng = np.random.default_rng(6)
        p = rng.integers(2, 200, size=7)

        def run(paged):
            eng = ServingEngine(model, params, max_batch=1, max_len=64,
                                seed=11, paged=paged)
            uid = eng.submit(p, max_new_tokens=6, temperature=0.8)
            return eng.run()[uid]

        assert run(True) == run(False)

    @pytest.mark.parametrize("plen,chunk,why", [
        (24, 24, "prompt exactly one prefill chunk"),
        (48, 24, "prompt exactly two prefill chunks"),
        (32, 24, "prompt a multiple of block_size (16), mid-chunk"),
        (16, 24, "prompt exactly one block, shorter than a chunk"),
        (7, 24, "prompt shorter than one chunk and one block"),
    ])
    def test_chunk_boundary_prompts_match_dense(self, tiny_lm, plen, chunk,
                                                why):
        """Chunked-prefill boundary cases: a prompt landing exactly on the
        prefill-chunk edge, exactly on a block_size multiple, or inside a
        single chunk must all produce the dense-slab greedy tokens (the
        last chunk's nvalid/causality masking is where off-by-ones live)."""
        model, params = tiny_lm
        rng = np.random.default_rng(20 + plen)
        p = rng.integers(2, 200, size=plen)
        dense = _solo(model, params, p, 6, paged=False)
        paged = _solo(model, params, p, 6, paged=True, prefill_chunk=chunk)
        assert dense == paged, why

    def test_chunked_prefill_compiles_once(self, tiny_lm):
        """The fixed-shape chunk step compiles exactly once regardless of
        prompt-length mix (the dense path compiles once per bucket)."""
        model, params = tiny_lm
        rng = np.random.default_rng(7)
        eng = ServingEngine(model, params, max_batch=2, max_len=128,
                            paged=True, prefill_chunk=16)
        for n in (3, 17, 40, 100):
            eng.submit(rng.integers(2, 200, size=n), max_new_tokens=2)
        out = eng.run()
        assert len(out) == 4
        assert eng._chunk_step._cache_size() == 1


# ------------------------------------------------- pool pressure + reuse


class TestBlockPool:
    def test_admission_backpressure_on_exhaustion(self, tiny_lm):
        """A pool smaller than the aggregate demand must serialize
        admissions (FIFO) yet still complete every request correctly."""
        model, params = tiny_lm
        rng = np.random.default_rng(8)
        prompts = [rng.integers(2, 200, size=20) for _ in range(3)]
        # Each request reserves ceil((20+13)/16) = 3 blocks; pool of 3 ->
        # one request in flight at a time despite 2 free slots.
        eng = ServingEngine(model, params, max_batch=2, max_len=64,
                            paged=True, num_blocks=3)
        uids = [eng.submit(p, max_new_tokens=13) for p in prompts]
        out = eng.run()
        assert eng.kv.alloc.peak_in_use <= 3
        for uid, p in zip(uids, prompts):
            assert out[uid] == _solo(model, params, p, 13)

    def test_oversized_request_rejected_at_submit(self, tiny_lm):
        """A worst case exceeding the TOTAL pool can never be admitted;
        submit() fails fast instead of letting it stall the FIFO head."""
        model, params = tiny_lm
        eng = ServingEngine(model, params, max_batch=1, max_len=64,
                            paged=True, num_blocks=1)
        with pytest.raises(ValueError, match="blocks"):
            eng.submit(np.arange(2, 22), max_new_tokens=13)  # needs 3 blocks

    def test_blocks_freed_and_reused_after_completion(self, tiny_lm):
        model, params = tiny_lm
        rng = np.random.default_rng(9)
        eng = ServingEngine(model, params, max_batch=2, max_len=64,
                            paged=True, num_blocks=4)
        first = [eng.submit(rng.integers(2, 200, size=9), max_new_tokens=4)
                 for _ in range(2)]
        eng.run()
        assert eng.kv.alloc.in_use() == 0
        assert (eng.kv.table_np == -1).all()
        peak = eng.kv.alloc.peak_in_use
        # Same engine, second wave: must reuse the freed blocks in place.
        p = rng.integers(2, 200, size=9)
        uid = eng.submit(p, max_new_tokens=4)
        out = eng.run()
        assert out[uid] == _solo(model, params, p, 4)
        assert eng.kv.alloc.peak_in_use == peak
        assert eng.kv.alloc.in_use() == 0

    def test_defrag_mid_flight_preserves_decode(self, tiny_lm):
        """Compacting live blocks (pool permutation + table rewrite) must
        not change any in-flight request's outputs."""
        model, params = tiny_lm
        rng = np.random.default_rng(10)
        prompts = [rng.integers(2, 200, size=n) for n in (18, 5)]

        eng = ServingEngine(model, params, max_batch=2, max_len=64,
                            paged=True)
        uids = [eng.submit(p, max_new_tokens=8) for p in prompts]
        eng._admit()
        for _ in range(3):
            eng.step()
        moved = eng.defrag()
        out = eng.run()
        assert moved >= 0  # bookkeeping ran; moves depend on layout
        for uid, p in zip(uids, prompts):
            assert out[uid] == _solo(model, params, p, 8)

    def test_hbm_scales_with_pool_not_slab(self, tiny_lm):
        model, params = tiny_lm
        dense = ServingEngine(model, params, max_batch=8, max_len=256,
                              paged=False)
        paged = ServingEngine(model, params, max_batch=8, max_len=256,
                              paged=True, num_blocks=24)
        db = dense.cache_stats()["cache_hbm_bytes"]
        pb = paged.cache_stats()["cache_hbm_bytes"]
        assert pb * 4 < db  # 24*16 tokens vs 8*256 slab rows


# --------------------------------------------------- donation + EOS exit


class TestDonatedJitRoots:
    def test_dense_decode_updates_cache_in_place(self, tiny_lm):
        """donate_argnums on the decode root: the step must reuse the cache
        buffer (no per-step reallocation) and invalidate the donated input."""
        model, params = tiny_lm
        eng = ServingEngine(model, params, max_batch=2, max_len=64,
                            paged=False)
        eng.submit(np.arange(2, 10), max_new_tokens=8)
        eng._admit()
        before = jax.tree.leaves(eng.cache)[0]
        ptr = before.unsafe_buffer_pointer()
        eng.step()
        eng.step()
        assert before.is_deleted()
        assert jax.tree.leaves(eng.cache)[0].unsafe_buffer_pointer() == ptr

    def test_paged_decode_updates_pools_in_place(self, tiny_lm):
        model, params = tiny_lm
        eng = ServingEngine(model, params, max_batch=2, max_len=64,
                            paged=True)
        eng.submit(np.arange(2, 10), max_new_tokens=8)
        eng._admit()
        before = jax.tree.leaves(eng.kv.pools)[0]
        ptr = before.unsafe_buffer_pointer()
        eng.step()
        eng.step()
        assert before.is_deleted()
        assert jax.tree.leaves(eng.kv.pools)[0].unsafe_buffer_pointer() == ptr


class TestDeviceEOS:
    @pytest.mark.parametrize("paged", [False, True])
    def test_eos_truncates_and_stops_row_on_device(self, tiny_lm, paged):
        model, params = tiny_lm
        rng = np.random.default_rng(11)
        p = rng.integers(2, 200, size=7)
        full = _solo(model, params, p, 8, paged=paged)
        # The eos token must first occur in the stream at index i >= 1 (a
        # token seen earlier would stop the row there instead).
        i = next(j for j in range(1, len(full)) if full[j] not in full[:j])
        eos = full[i]

        eng = ServingEngine(model, params, max_batch=1, max_len=64,
                            paged=paged)
        uid = eng.submit(p, max_new_tokens=8, eos_id=eos)
        out = eng.run()
        assert out[uid] == full[:i + 1]  # stops at (and includes) eos
        # Device-side exit: the row's active flag was cleared ON DEVICE in
        # the same step that sampled eos, and its cache_len stopped.
        assert not bool(np.asarray(eng._active_dev)[0])
        assert int(np.asarray(eng.cache_len)[0]) == len(p) + i

    def test_eos_row_stops_while_others_continue(self, tiny_lm):
        model, params = tiny_lm
        rng = np.random.default_rng(12)
        p_a, p_b = (rng.integers(2, 200, size=n) for n in (7, 9))
        full_a = _solo(model, params, p_a, 8)
        eng = ServingEngine(model, params, max_batch=2, max_len=64)
        uid_a = eng.submit(p_a, max_new_tokens=8, eos_id=full_a[1])
        uid_b = eng.submit(p_b, max_new_tokens=8)
        out = eng.run()
        assert out[uid_a] == full_a[:2]
        assert out[uid_b] == _solo(model, params, p_b, 8)


# ------------------------------------------------------ layout + routing


class TestCacheLayout:
    def test_attention_models_paged(self, tiny_lm):
        model, _ = tiny_lm
        assert cache_layout(model) == "paged"

    @pytest.mark.parametrize("name", [
        "rwkv6-1.6b",        # recurrent state
        "moonshot-v1-16b-a3b",  # token-choice MoE
        "minicpm3-4b",       # MLA latent cache
    ])
    def test_non_pageable_models_dense(self, name):
        from repro.configs import get_config

        model = build_model(get_config(name).reduced())
        assert cache_layout(model) == "dense"

    def test_paged_cache_init_rejects_non_attention(self):
        from repro.configs import get_config

        model = build_model(get_config("rwkv6-1.6b").reduced())
        with pytest.raises(ValueError, match="paged"):
            model.init_paged_cache(4, 16)


class TestMoEKernelRouting:
    def test_nested_experts_route_through_ops(self):
        """_expert_ffn's nested factored path must dispatch through
        kernels.nested_lowrank.ops (vmapped over experts) and agree with
        the stacked-einsum math."""
        from repro.kernels.nested_lowrank import ops as nlr_ops
        from repro.models import moe as moe_mod

        rng = np.random.default_rng(13)
        e, c, d, f, k1, k2 = 4, 8, 32, 48, 8, 2
        mk = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.2, jnp.float32)

        def factors(i, o):
            return {"u": mk(e, i, k1), "v": mk(e, k1, o),
                    "u2": mk(e, i, k2), "v2": mk(e, k2, o)}

        experts = {"wi": factors(d, f), "wg": factors(d, f),
                   "wo": factors(f, d)}
        buf = mk(e, c, d)

        calls = []
        real = nlr_ops.nested_lowrank_matmul

        def spy(*a, **kw):
            calls.append(1)
            return real(*a, **kw)

        with mock.patch.object(nlr_ops, "nested_lowrank_matmul",
                               side_effect=spy):
            out, _ = moe_mod._expert_ffn(experts, buf)
        assert calls  # routed through the ops dispatch

        def emm(p, hh):
            y = jnp.einsum("eck,ekf->ecf",
                           jnp.einsum("ecd,edk->eck", hh, p["u"]), p["v"])
            return y + jnp.einsum(
                "eck,ekf->ecf", jnp.einsum("ecd,edk->eck", hh, p["u2"]), p["v2"]
            )

        h = jax.nn.silu(emm(experts["wg"], buf)) * emm(experts["wi"], buf)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(emm(experts["wo"], h)),
            rtol=1e-5, atol=1e-5,
        )

    def test_moe_model_forward_with_nested_params_finite(self):
        """End-to-end: a compressed MoE model still runs through the routed
        expert path."""
        from repro.configs import get_config

        cfg = get_config("moonshot-v1-16b-a3b").reduced()
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (2, 8), 0,
                                    cfg.vocab_size)
        logits, _, _ = model.apply(params, tokens, mode="train")
        assert jnp.isfinite(logits).all()


class TestPagedKVCacheUnit:
    def test_reserve_free_table_roundtrip(self, tiny_lm):
        model, _ = tiny_lm
        kv = PagedKVCache(model, max_batch=2, max_len=64, block_size=16,
                          num_blocks=4)
        assert kv.reserve(0, 33)  # 3 blocks
        assert not kv.reserve(1, 33)  # only 1 left
        assert kv.reserve(1, 10)  # 1 block fits
        assert (kv.table_np >= 0).sum() == 4
        kv.free(0)
        assert (kv.table_np[0] == -1).all()
        assert kv.alloc.in_use() == 1

    def test_stats_account_pool_bytes(self, tiny_lm):
        model, _ = tiny_lm
        kv = PagedKVCache(model, max_batch=2, max_len=64, block_size=16,
                          num_blocks=4)
        s = kv.stats()
        assert s["tokens_capacity"] == 64
        leaf_bytes = sum(l.nbytes for l in jax.tree.leaves(kv.pools))
        assert s["cache_hbm_bytes"] == leaf_bytes
