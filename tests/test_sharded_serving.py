"""Mesh-sharded serving regression tests.

Pins the two SPMD contracts the engine guarantees:

  * a (1, 1) serving mesh is BIT-FOR-BIT the meshless single-device path
    (runs everywhere, including the plain 1-device tier), and
  * a (2, 2) DP x TP mesh — weights tensor-parallel, slots/pools
    data-parallel with per-shard block ranges — serves token-identically
    (greedy AND temperature AND speculative) to the single-device engine
    on both cache layouts, with the donation and one-D2H-per-step
    contracts intact.

The (2, 2) tests need 4 devices: run with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (the dedicated CI
job does); on a 1-device host they skip.  Shard-aware BlockAllocator
bookkeeping (per-shard free lists, peaks, shard-local defrag) is pure host
logic and runs everywhere."""

from unittest import mock

import jax
import numpy as np
import pytest

from repro.configs.paper_models import small_lm
from repro.launch.mesh import make_serving_mesh
from repro.models import build_model
from repro.parallel.sharding import make_parallelism
from repro.serving.engine import ServingEngine
from repro.serving.kvcache import BlockAllocator
from repro.serving.spec import SpecConfig

VOCAB = 256

need4 = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=4",
)


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = small_lm(name="tiny-sharded", vocab_size=VOCAB, num_layers=2,
                   d_model=64, d_ff=96, num_heads=4)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return model, params


@pytest.fixture(scope="module")
def draft_params(tiny_lm):
    """Perturbed weights stand in for a higher-ratio NSVD twin (same pytree
    structure, different logits — exercises real rejections/rollbacks)."""
    _, params = tiny_lm
    k = jax.random.key(99)
    return jax.tree.map(
        lambda x: x + 0.02 * jax.random.normal(k, x.shape, x.dtype)
        if x.ndim >= 2 else x,
        params,
    )


@pytest.fixture(scope="module")
def par22():
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    return make_parallelism(make_serving_mesh(2, 2))


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(1)
    return [rng.integers(2, 200, size=n) for n in (6, 9, 5, 7)]


def _serve(model, params, prompts, par=None, max_new=6, temperature=0.0,
           **kw):
    eng = ServingEngine(model, params, max_batch=4, max_len=64,
                        parallelism=par, **kw)
    uids = [eng.submit(p, max_new_tokens=max_new, temperature=temperature)
            for p in prompts]
    out = eng.run()
    return [out[u] for u in uids], eng


# ------------------------------------------------------------ mesh factory


class TestMakeServingMesh:
    def test_oversubscribed_mesh_warns_and_falls_back_to_11(self):
        """An oversubscribed mesh no longer degrades to (1, 1): it raises,
        so a run that asked for dp x tp never silently serves on one
        device."""
        with pytest.raises(ValueError, match="needs"):
            make_serving_mesh(jax.device_count() + 1, 1)

    def test_rejects_nonpositive_axes(self):
        with pytest.raises(ValueError, match="positive"):
            make_serving_mesh(0, 2)

    @need4
    def test_22_mesh_on_four_devices(self):
        mesh = make_serving_mesh(2, 2)
        assert dict(mesh.shape) == {"data": 2, "model": 2}


# --------------------------------------------------- shard-aware allocator


class TestShardedBlockAllocator:
    def test_single_shard_matches_legacy_behavior(self):
        a = BlockAllocator(8)
        assert a.alloc("r", 3) == [0, 1, 2]
        assert a.free("r") == [0, 1, 2]
        assert a.peak_in_use == 3 and a.peak_by_shard == [3]

    def test_per_shard_ranges_and_backpressure(self):
        a = BlockAllocator(8, num_shards=2)
        assert a.alloc("r0", 3, shard=0) == [0, 1, 2]
        assert a.alloc("r1", 3, shard=1) == [4, 5, 6]
        # Shard 0 has one block left: a 2-block ask backpressures even
        # though the OTHER shard could serve it.
        assert a.alloc("r2", 2, shard=0) is None
        assert a.alloc("r2", 1, shard=1) == [7]
        assert a.in_use() == 7
        assert a.in_use(0) == 3 and a.in_use(1) == 4

    def test_free_returns_blocks_to_home_shards(self):
        a = BlockAllocator(8, num_shards=2)
        a.alloc("r", 2, shard=0)
        a.alloc("r", 2, shard=1)  # one owner spanning shards
        a.free("r")
        assert a.free_blocks(0) == 4 and a.free_blocks(1) == 4
        assert a.alloc("x", 4, shard=1) == [4, 5, 6, 7]

    def test_peak_accounting_per_shard_and_aggregate(self):
        a = BlockAllocator(8, num_shards=2)
        a.alloc("r0", 3, shard=0)
        a.free("r0")
        a.alloc("r1", 2, shard=1)
        # Aggregate peak (3) is NOT the sum of per-shard peaks (3 + 2):
        # the shards peaked at different times.
        assert a.peak_in_use == 3
        assert a.peak_by_shard == [3, 2]

    def test_defrag_is_shard_local(self):
        a = BlockAllocator(8, num_shards=2)
        a.alloc("r0", 2, shard=0)
        a.alloc("r1", 2, shard=1)   # blocks 4, 5
        a.alloc("r2", 1, shard=1)   # block 6
        a.free("r1")
        moves = a.defrag()
        # r2's block compacts to the bottom OF ITS SHARD (4), never to
        # shard 0's free ids 2..3.
        assert moves == {6: 4}
        assert a.owned_by("r2") == [4]
        assert a.free_blocks(0) == 2 and a.free_blocks(1) == 3

    def test_rejects_indivisible_sharding(self):
        with pytest.raises(ValueError, match="divisible"):
            BlockAllocator(7, num_shards=2)


# --------------------------------------------- (1,1) mesh == meshless path


class TestMesh11Equivalence:
    """The invariant every other mesh test builds on: a (1, 1) mesh changes
    nothing — same tokens, same layouts, same stats."""

    def test_bitwise_equal_tokens_both_layouts(self, tiny_lm, prompts):
        model, params = tiny_lm
        par11 = make_parallelism(make_serving_mesh(1, 1))
        for paged in (True, False):
            base, be = _serve(model, params, prompts, paged=paged)
            mesh, me = _serve(model, params, prompts, par=par11, paged=paged)
            assert mesh == base
            assert me.dp_shards == 1
            assert me.cache_stats()["mesh"] == {"dp": 1, "tp": 1,
                                                "devices": 1}
            assert (me.cache_stats()["per_device_cache_hbm_bytes"]
                    == be.cache_stats()["cache_hbm_bytes"])


# ------------------------------------------------- (2,2) DP x TP SPMD path


@need4
class TestSharded22Equivalence:
    def test_greedy_identical_both_layouts(self, tiny_lm, prompts, par22):
        model, params = tiny_lm
        for paged in (True, False):
            base, _ = _serve(model, params, prompts, paged=paged)
            shard, eng = _serve(model, params, prompts, par=par22,
                                paged=paged)
            assert shard == base, f"paged={paged}"
            assert eng.dp_shards == 2
            assert eng.cache_stats()["mesh"] == {"dp": 2, "tp": 2,
                                                 "devices": 4}

    def test_tick_rungs_identical(self, tiny_lm, par22):
        """max_batch 8 over DP 2 ticks at rungs 2 and 8 (multiples of the
        DP shards): the sharded chunk root at either rung gives the
        meshless tokens."""
        model, params = tiny_lm
        rng = np.random.default_rng(9)
        groups = [[rng.integers(2, 200, size=n)] for n in (20, 17)]
        groups.append([rng.integers(2, 200, size=n)
                       for n in (18, 25, 9, 30)])

        def serve(par):
            eng = ServingEngine(model, params, max_batch=8, max_len=64,
                                paged=True, prefill_chunk=8, parallelism=par)
            uids = []
            for group in groups:
                uids += [eng.submit(p, max_new_tokens=5) for p in group]
                eng.run(max_steps=1)
            eng.run()
            return eng, [eng.finished_requests[u].generated for u in uids]

        _, base = serve(None)
        eng, shard = serve(par22)
        assert eng._tick_rungs == (2, 8)
        assert eng._chunk_step._cache_size() == 2
        assert shard == base

    def test_temperature_sampling_identical_both_layouts(self, tiny_lm,
                                                         prompts, par22):
        """Per-slot PRNG keys are slot state, so sharding must not change
        the sampled stream."""
        model, params = tiny_lm
        for paged in (True, False):
            base, _ = _serve(model, params, prompts, paged=paged,
                             temperature=0.7)
            shard, _ = _serve(model, params, prompts, par=par22,
                              paged=paged, temperature=0.7)
            assert shard == base, f"paged={paged}"

    def test_int8_kv_quant_identical(self, tiny_lm, prompts, par22):
        model, params = tiny_lm
        base, _ = _serve(model, params, prompts, paged=True, kv_quant=True)
        shard, _ = _serve(model, params, prompts, par=par22, paged=True,
                          kv_quant=True)
        assert shard == base

    def test_spec_decoding_identical_both_layouts(self, tiny_lm, prompts,
                                                  par22, draft_params):
        """Speculative draft+verify (including per-step cache-length
        rollback of rejected proposals) under the mesh: same committed
        tokens AND same acceptance accounting as the unsharded engine."""
        model, params = tiny_lm
        spec = SpecConfig(draft_params=draft_params, k=3)
        for paged in (True, False):
            plain, _ = _serve(model, params, prompts, paged=paged)
            base, b_eng = _serve(model, params, prompts, paged=paged,
                                 spec_config=spec)
            shard, s_eng = _serve(model, params, prompts, par=par22,
                                  paged=paged, spec_config=spec)
            assert shard == plain == base, f"paged={paged}"
            bs, ss = b_eng.spec_stats(), s_eng.spec_stats()
            assert (ss["proposed"], ss["accepted"], ss["committed"]) == \
                (bs["proposed"], bs["accepted"], bs["committed"])

    def test_mid_flight_defrag_with_spec_rollback(self, tiny_lm, prompts,
                                                  par22, draft_params):
        """Shard-local defrag (block-diagonal donated permutation of BOTH
        sharded pools) between speculative steps must not change a single
        committed token."""
        model, params = tiny_lm
        spec = SpecConfig(draft_params=draft_params, k=3)
        base, _ = _serve(model, params, prompts, spec_config=spec)

        eng = ServingEngine(model, params, max_batch=4, max_len=64,
                            parallelism=par22, spec_config=spec)
        uids = [eng.submit(p, max_new_tokens=6) for p in prompts]
        finished = {}
        for step in range(200):
            for r in eng._admit():
                finished[r.uid] = r.generated
            if not eng.active.any():
                if not eng.queue and not eng._prefilling:
                    break
                continue
            for r in eng.step():
                finished[r.uid] = r.generated
            eng.defrag()  # compact target + draft pools mid-flight
        assert [finished[u] for u in uids] == base

    def test_sharded_pools_donated_in_place(self, tiny_lm, prompts, par22):
        """Donation must survive explicit NamedShardings: every per-shard
        buffer of the block pools is reused across decode steps."""
        model, params = tiny_lm
        eng = ServingEngine(model, params, max_batch=4, max_len=64,
                            parallelism=par22)
        eng.submit(prompts[0], max_new_tokens=8)
        eng._admit()
        leaf = jax.tree.leaves(eng.kv.pools)[0]
        assert len(leaf.sharding.device_set) == 4
        ptrs = sorted(s.data.unsafe_buffer_pointer()
                      for s in leaf.addressable_shards)
        eng.step()
        after = sorted(s.data.unsafe_buffer_pointer()
                       for s in jax.tree.leaves(eng.kv.pools)[0]
                       .addressable_shards)
        assert after == ptrs

    def test_sharded_dense_slab_donated_in_place(self, tiny_lm, prompts,
                                                 par22):
        model, params = tiny_lm
        eng = ServingEngine(model, params, max_batch=4, max_len=64,
                            paged=False, parallelism=par22)
        eng.submit(prompts[0], max_new_tokens=8)
        eng._admit()
        leaf = jax.tree.leaves(eng.cache)[0]
        ptrs = sorted(s.data.unsafe_buffer_pointer()
                      for s in leaf.addressable_shards)
        eng.step()
        after = sorted(s.data.unsafe_buffer_pointer()
                       for s in jax.tree.leaves(eng.cache)[0]
                       .addressable_shards)
        assert after == ptrs

    def test_exactly_one_device_to_host_transfer_per_step(self, tiny_lm,
                                                          prompts, par22):
        """Sampled tokens leave through ONE sharded D2H transfer, not one
        per shard."""
        model, params = tiny_lm
        eng = ServingEngine(model, params, max_batch=4, max_len=64,
                            parallelism=par22, pipeline_depth=1)
        for p in prompts:
            eng.submit(p, max_new_tokens=8)
        eng._admit()
        real = jax.device_get
        calls = []

        def counting(x):
            calls.append(1)
            return real(x)

        with mock.patch.object(jax, "device_get", side_effect=counting):
            for _ in range(4):
                eng.step()
        assert len(calls) == 4

    def test_pipelined_depth2_identical_under_mesh(self, tiny_lm, prompts,
                                                   par22, draft_params):
        """The depth-2 step pipeline composes with SPMD: greedy,
        temperature (slot-reusing workload) and speculative streams under
        a (2, 2) mesh match the depth-1 sharded engine on both layouts,
        consuming at most one sharded D2H per step."""
        model, params = tiny_lm
        extra = [np.asarray(p[::-1]) for p in prompts]  # force slot reuse
        work = list(prompts) + extra
        lens = [6, 4, 7, 3, 5, 6, 4, 5]

        def serve(depth, temperature=0.0, spec=None, paged=True):
            eng = ServingEngine(model, params, max_batch=4, max_len=64,
                                parallelism=par22, paged=paged,
                                spec_config=spec, pipeline_depth=depth)
            uids = [eng.submit(p, max_new_tokens=m, temperature=temperature)
                    for p, m in zip(work, lens)]
            out = eng.run()
            assert eng.decode_transfers == len(eng.step_times)
            return [out[u] for u in uids]

        for paged in (True, False):
            assert serve(2, paged=paged) == serve(1, paged=paged)
            assert (serve(2, temperature=0.7, paged=paged)
                    == serve(1, temperature=0.7, paged=paged))
        spec = SpecConfig(draft_params=draft_params, k=3)
        assert serve(2, spec=spec) == serve(1, spec=spec)

    def test_weights_are_tensor_sharded(self, tiny_lm, par22):
        """TP actually engages: attention projections shard over 'model'."""
        model, params = tiny_lm
        eng = ServingEngine(model, params, max_batch=4, max_len=64,
                            parallelism=par22)
        wq = eng.params["g0"]["sub0"]["attn"]["wq"]["kernel"]
        assert "model" in str(wq.sharding.spec)
        assert len(wq.sharding.device_set) == 4

    def test_per_shard_admission_and_peaks(self, tiny_lm, par22):
        """Slots map to DP shards; reservations come from the slot's shard
        range and per-shard peaks stay within the sub-pool."""
        model, params = tiny_lm
        rng = np.random.default_rng(5)
        eng = ServingEngine(model, params, max_batch=4, max_len=64,
                            paged=True, num_blocks=8, parallelism=par22)
        assert eng.kv.dp_shards == 2 and eng.kv.blocks_per_shard == 4
        uids = [eng.submit(rng.integers(2, 200, size=9), max_new_tokens=4)
                for _ in range(4)]
        out = eng.run()
        assert len(out) == len(uids)
        st = eng.kv.stats()
        assert len(st["blocks_peak_by_shard"]) == 2
        assert all(0 < p <= 4 for p in st["blocks_peak_by_shard"])
        assert st["per_device_cache_hbm_bytes"] * 2 == st["cache_hbm_bytes"]

    def test_pad_sensitive_exact_length_prefill_under_mesh(self, par22):
        """Recurrent caches fall back to exact-length rows=1 admission,
        which cannot split over DP: those inputs stay replicated while
        slot state keeps its sharding — and tokens still match the
        meshless engine."""
        from repro.configs import get_config

        cfg = get_config("rwkv6-1.6b").reduced()
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        rng = np.random.default_rng(11)
        ps = [rng.integers(2, 200, size=n) for n in (5, 6)]

        def serve(par):
            eng = ServingEngine(model, params, max_batch=2, max_len=64,
                                parallelism=par)
            assert not eng._bucketed
            uids = [eng.submit(p, max_new_tokens=3) for p in ps]
            out = eng.run()
            return [out[u] for u in uids]

        assert serve(par22) == serve(None)

    def test_indivisible_max_batch_keeps_tp_drops_dp(self, tiny_lm, prompts,
                                                     par22):
        """max_batch=3 doesn't divide dp=2: slots/pools fall back to
        replicated (single-shard bookkeeping) while weights stay TP — and
        tokens still match the meshless engine."""
        model, params = tiny_lm

        def serve3(par):
            eng = ServingEngine(model, params, max_batch=3, max_len=64,
                                parallelism=par)
            uids = [eng.submit(p, max_new_tokens=6) for p in prompts]
            out = eng.run()
            return [out[u] for u in uids], eng

        base, _ = serve3(None)
        shard, eng = serve3(par22)
        assert shard == base
        assert eng.dp_shards == 1 and eng.kv.dp_shards == 1
        wq = eng.params["g0"]["sub0"]["attn"]["wq"]["kernel"]
        assert "model" in str(wq.sharding.spec)

    def test_submit_rejects_worst_case_exceeding_shard_subpool(self, tiny_lm,
                                                               par22):
        """With the pool split over DP shards, the admissibility bound is
        the per-shard sub-pool, not the global block count."""
        model, params = tiny_lm
        eng = ServingEngine(model, params, max_batch=4, max_len=64,
                            paged=True, num_blocks=4, parallelism=par22)
        with pytest.raises(ValueError, match="shard"):
            eng.submit(np.arange(2, 22), max_new_tokens=13)  # needs 3 > 2


# ------------------------------------------- Pallas kernels under a mesh


_MESHES = [(1, 1), pytest.param(2, 2, marks=need4),
           pytest.param(4, 1, marks=need4)]


class TestKernelsUnderMesh:
    """Mosaic kernels cannot be partitioned automatically, so under a mesh
    the ops wrappers run them per device in a shard_map.  Here the kernels
    run in interpret mode and must match the unsharded oracle."""

    @pytest.mark.parametrize("quant", [False, True], ids=["f32", "int8"])
    @pytest.mark.parametrize("dp,tp", _MESHES)
    def test_paged_attention_per_device_matches_oracle(self, dp, tp, quant):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.kernels.paged_attention import ops
        from repro.parallel.sharding import tracing_with

        par = make_parallelism(make_serving_mesh(dp, tp))
        b, hq, hkv, hd, bs, n, m = 4, 4, 2, 16, 4, 16, 4
        rng = np.random.default_rng(dp * 10 + tp)
        # Like the engine's allocator: each DP shard's rows hold blocks of
        # that shard's contiguous block range only; -1 pads table tails.
        per_shard, rows_per_shard = n // dp, b // dp
        tables = np.full((b, m), -1, np.int32)
        lengths = rng.integers(1, m * bs + 1, size=b).astype(np.int32)
        for r in range(b):
            d = r // rows_per_shard
            used = -(-int(lengths[r]) // bs)
            tables[r, :used] = rng.permutation(per_shard)[:used] + d * per_shard
        pool = (n, bs, hkv, hd)
        q = rng.standard_normal((b, hq, hd)).astype(np.float32)
        if quant:
            kp, vp = (rng.integers(-127, 127, pool).astype(np.int8)
                      for _ in range(2))
            scales = tuple(rng.uniform(0.01, 0.05, pool[:3]).astype(
                np.float32) for _ in range(2))
        else:
            kp, vp = (rng.standard_normal(pool).astype(np.float32)
                      for _ in range(2))
            scales = ()
        args = (q, kp, vp, tables, lengths, *scales)

        def placed(a):
            spec = P("data", *([None] * (a.ndim - 1)))
            return jax.device_put(a, NamedSharding(par.mesh, spec))

        def step(*a):
            with tracing_with(par):
                return ops.paged_attention(*a[:5], *a[5:], interpret=True)

        got = jax.jit(step)(*(placed(a) for a in args))
        want = ops.paged_attention_ref(*args[:5], *scales)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("dp,tp", _MESHES)
    def test_nested_lowrank_per_device_matches_oracle(self, dp, tp):
        from repro.kernels.nested_lowrank import ops
        from repro.parallel.sharding import tracing_with

        par = make_parallelism(make_serving_mesh(dp, tp))
        rng = np.random.default_rng(7)
        x, u, v, u2, v2 = (rng.standard_normal(s).astype(np.float32)
                           for s in ((8, 64), (64, 24), (24, 96),
                                     (64, 8), (8, 96)))

        def step(*a):
            with tracing_with(par):
                return ops.nested_lowrank_matmul(*a, block_n=32,
                                                 interpret=True)

        got = jax.jit(step)(x, u, v, u2, v2)
        want = ops.nested_lowrank_matmul_ref(x, u, v, u2, v2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)

    @need4
    def test_nested_lowrank_takes_xla_path_under_tensor_parallelism(
            self, monkeypatch):
        """Under TP a psum must sit between the two factor matmuls, which
        the fused kernel cannot hold: the automatic route is XLA even on a
        TPU, and the kernel where TP is 1."""
        from repro.kernels.nested_lowrank import ops
        from repro.parallel.sharding import tracing_with

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        shapes = ((8, 64), np.dtype("float32"), (64, 24), (24, 96), (64, 8))
        assert ops.takes_kernel(*shapes)
        with tracing_with(make_parallelism(make_serving_mesh(4, 1))):
            assert ops.takes_kernel(*shapes)
        with tracing_with(make_parallelism(make_serving_mesh(2, 2))):
            assert not ops.takes_kernel(*shapes)


# ------------------------------------------------ bench schema migration


class TestBenchSchemaMigration:
    def test_schema2_entries_gain_mesh_and_pipeline_stamps(self, tmp_path):
        st = pytest.importorskip("benchmarks.serving_throughput")
        import json

        path = tmp_path / "BENCH_serving.json"
        old = {"schema": 2, "history": [
            {"git_sha": "abc", "rows": [{"label": "dense",
                                         "cache_hbm_bytes": 100}]},
        ]}
        path.write_text(json.dumps(old))
        doc = st.append_history(
            {"git_sha": "def", "mesh": {"dp": 2, "tp": 2, "devices": 4},
             "rows": []},
            path=str(path),
        )
        assert doc["schema"] == st.BENCH_SCHEMA == 8
        migrated, fresh = doc["history"]
        assert migrated["mesh"] == {"dp": 1, "tp": 1, "devices": 1}
        assert migrated["rows"][0]["per_device_cache_bytes"] == 100
        # Schema 3 -> 4: pre-pipeline rows ran the serial loop (depth 1)
        # with no device-wait/host breakdown recorded.
        assert migrated["rows"][0]["pipeline_depth"] == 1
        assert migrated["rows"][0]["step_device_wait_ms"] is None
        # Schema 4 -> 5: pre-auditor entries carry a null contract stamp.
        assert migrated["audit"] is None
        # Schema 5 -> 6: pre-observability entries carry null telemetry
        # and roofline blocks.
        assert migrated["telemetry"] is None
        assert migrated["roofline"] is None
        # Schema 6 -> 7: pre-scheduler rows ran worst-case admission with
        # no live-occupancy or preemption accounting.
        assert migrated["rows"][0]["admission_policy"] == "worst_case"
        assert migrated["rows"][0]["occupancy_live_frac"] is None
        assert migrated["rows"][0]["preempt_count"] == 0
        # Schema 7 -> 8: pre-fault-tolerance entries carry a null faults
        # rollup (the engine ran with no injection surface at all).
        assert migrated["faults"] is None
        assert fresh["mesh"]["dp"] == 2
