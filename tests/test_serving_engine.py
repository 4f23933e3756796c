"""Serving-engine regression tests: slot reuse across admissions, batched
vs. sequential greedy equivalence, prefill bucket compile counts, and the
one-transfer-per-step contract."""

from unittest import mock

import jax
import numpy as np
import pytest

from repro.configs.paper_models import small_lm
from repro.models import build_model
from repro.serving.engine import ServingEngine

VOCAB = 256


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = small_lm(name="tiny-serve", vocab_size=VOCAB, num_layers=2,
                   d_model=64, d_ff=96, num_heads=4)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return model, params


def _solo(model, params, prompt, max_new, max_len=64):
    eng = ServingEngine(model, params, max_batch=1, max_len=max_len)
    uid = eng.submit(prompt, max_new_tokens=max_new)
    return eng.run()[uid]


class TestSlotReuse:
    def test_new_request_does_not_see_previous_occupants_kv(self, tiny_lm):
        """A slot freed by a finished request must be fully re-initialized:
        the next occupant's generations must match a fresh single-request
        run (stale KV rows from the previous occupant would change them)."""
        model, params = tiny_lm
        rng = np.random.default_rng(1)
        long_p = rng.integers(2, 200, size=13)   # larger bucket, fills rows
        short_p = rng.integers(2, 200, size=5)

        eng = ServingEngine(model, params, max_batch=1, max_len=64)
        uid_a = eng.submit(long_p, max_new_tokens=6)
        uid_b = eng.submit(short_p, max_new_tokens=6)  # reuses slot 0
        out = eng.run()
        assert out[uid_b] == _solo(model, params, short_p, 6)
        assert out[uid_a] == _solo(model, params, long_p, 6)

    def test_mid_flight_admission_matches_solo(self, tiny_lm):
        """Requests admitted into a slot mid-flight (while another row keeps
        decoding) generate the same greedy tokens as a solo run."""
        model, params = tiny_lm
        rng = np.random.default_rng(2)
        prompts = [rng.integers(2, 200, size=n) for n in (6, 6, 7, 5)]
        lens = [9, 3, 5, 4]  # staggered finish -> slots free mid-flight

        eng = ServingEngine(model, params, max_batch=2, max_len=64)
        uids = [eng.submit(p, max_new_tokens=m) for p, m in zip(prompts, lens)]
        out = eng.run()
        for uid, p, m in zip(uids, prompts, lens):
            assert out[uid] == _solo(model, params, p, m), uid


class TestBatchedSampling:
    def test_batched_matches_sequential_at_temp0(self, tiny_lm):
        model, params = tiny_lm
        rng = np.random.default_rng(3)
        prompts = [rng.integers(2, 200, size=6) for _ in range(5)]
        eng = ServingEngine(model, params, max_batch=3, max_len=64)
        uids = [eng.submit(p, max_new_tokens=8) for p in prompts]
        out = eng.run()
        for uid, p in zip(uids, prompts):
            assert out[uid] == _solo(model, params, p, 8)

    def test_temperature_sampling_reproducible_and_in_vocab(self, tiny_lm):
        model, params = tiny_lm
        rng = np.random.default_rng(4)
        prompts = [rng.integers(2, 200, size=6) for _ in range(3)]

        def once():
            eng = ServingEngine(model, params, max_batch=2, max_len=64, seed=9)
            uids = [eng.submit(p, max_new_tokens=6, temperature=0.7)
                    for p in prompts]
            out = eng.run()
            return [out[u] for u in uids]

        a, b = once(), once()
        assert a == b
        assert all(0 <= t < VOCAB for toks in a for t in toks)


class TestPrefillBuckets:
    """Bucketed prefill is the DENSE-slab admission path (attention models
    default to the paged engine, whose chunked prefill compiles once per
    rung of its row ladder — see test_paged_kvcache.py); pin paged=False
    here."""

    def test_compilations_bounded_by_buckets_not_lengths(self, tiny_lm):
        """Prompts of lengths {7, 9, 250} span two power-of-two buckets
        (16 and 256): the prefill step must compile at most twice."""
        model, params = tiny_lm
        rng = np.random.default_rng(5)
        eng = ServingEngine(model, params, max_batch=2, max_len=512,
                            paged=False)
        for n in (7, 9, 250):
            eng.submit(rng.integers(2, 200, size=n), max_new_tokens=2)
        out = eng.run()
        assert len(out) == 3
        n_buckets_used = len({eng._bucket(n) for n in (7, 9, 250)})
        assert n_buckets_used == 2
        assert eng._prefill._cache_size() <= n_buckets_used

    def test_same_bucket_requests_prefill_together(self, tiny_lm):
        model, params = tiny_lm
        rng = np.random.default_rng(6)
        eng = ServingEngine(model, params, max_batch=4, max_len=64,
                            paged=False)
        for n in (5, 7, 9, 11):  # all bucket 16
            eng.submit(rng.integers(2, 200, size=n), max_new_tokens=2)
        eng.run()
        assert eng._prefill._cache_size() == 1


class TestSubmitValidation:
    def test_rejects_empty_prompt(self, tiny_lm):
        model, params = tiny_lm
        eng = ServingEngine(model, params, max_batch=1, max_len=64)
        with pytest.raises(ValueError, match="empty"):
            eng.submit(np.array([], np.int32))

    def test_rejects_oversized_prompt(self, tiny_lm):
        model, params = tiny_lm
        eng = ServingEngine(model, params, max_batch=1, max_len=64)
        with pytest.raises(ValueError, match="exceeds"):
            eng.submit(np.arange(2, 2 + 80))


class TestPadSensitiveFallback:
    def test_moe_models_do_not_bucket(self):
        """Token-choice MoE budgets expert capacity over the flattened
        token batch: right-padded prompts would evict real tokens from
        expert slots, so MoE engines must use exact-length prefill."""
        from repro.configs import get_config

        cfg = get_config("moonshot-v1-16b-a3b").reduced()
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        eng = ServingEngine(model, params, max_batch=2, max_len=64)
        assert not eng._bucketed
        rng = np.random.default_rng(8)
        uid = eng.submit(rng.integers(2, 200, size=6), max_new_tokens=3)
        out = eng.run()
        assert len(out[uid]) == 3

    def test_recurrent_models_do_not_bucket(self):
        from repro.configs import get_config

        cfg = get_config("rwkv6-1.6b").reduced()
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        eng = ServingEngine(model, params, max_batch=2, max_len=64)
        assert not eng._bucketed

    def test_attention_models_bucket(self, tiny_lm):
        model, params = tiny_lm
        eng = ServingEngine(model, params, max_batch=2, max_len=64,
                            paged=False)
        assert eng._bucketed

    def test_attention_models_default_to_paged(self, tiny_lm):
        model, params = tiny_lm
        eng = ServingEngine(model, params, max_batch=2, max_len=64)
        assert eng.paged

    def test_pad_sensitive_models_default_to_dense(self):
        from repro.configs import get_config

        cfg = get_config("moonshot-v1-16b-a3b").reduced()
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        eng = ServingEngine(model, params, max_batch=2, max_len=64)
        assert not eng.paged
        with pytest.raises(ValueError, match="cache layout"):
            ServingEngine(model, params, max_batch=2, max_len=64, paged=True)


class TestSyncFreeDecode:
    def test_exactly_one_device_to_host_transfer_per_step(self, tiny_lm):
        """Depth-1 pipeline == today's unpipelined engine: every step() is
        one dispatch followed by exactly one consumed transfer."""
        model, params = tiny_lm
        rng = np.random.default_rng(7)
        eng = ServingEngine(model, params, max_batch=2, max_len=64,
                            pipeline_depth=1)
        for _ in range(2):
            eng.submit(rng.integers(2, 200, size=6), max_new_tokens=8)
        eng._admit()

        real = jax.device_get
        calls = []

        def counting(x):
            calls.append(1)
            return real(x)

        with mock.patch.object(jax, "device_get", side_effect=counting):
            for _ in range(4):
                eng.step()
        assert len(calls) == 4  # one transfer per decode step, not per slot

    def test_pipelined_steps_consume_at_most_one_transfer(self, tiny_lm):
        """Depth 2: the first step only dispatches (no sync at all); every
        later step consumes exactly the one oldest transfer, and drain()
        flushes the remaining in-flight step."""
        model, params = tiny_lm
        rng = np.random.default_rng(7)
        eng = ServingEngine(model, params, max_batch=2, max_len=64,
                            pipeline_depth=2)
        for _ in range(2):
            eng.submit(rng.integers(2, 200, size=6), max_new_tokens=8)
        eng._admit()

        real = jax.device_get
        calls = []

        def counting(x):
            calls.append(1)
            return real(x)

        with mock.patch.object(jax, "device_get", side_effect=counting):
            per_step = []
            for _ in range(4):
                before = len(calls)
                eng.step()
                per_step.append(len(calls) - before)
            eng.drain()
        assert per_step == [0, 1, 1, 1]  # device runs one step ahead
        assert len(calls) == 4  # drain syncs the ring's last entry

    def test_transfer_counter_tracks_steps(self, tiny_lm):
        model, params = tiny_lm
        eng = ServingEngine(model, params, max_batch=2, max_len=64)
        eng.submit(np.arange(2, 8), max_new_tokens=5)
        eng.run()
        assert eng.decode_transfers == len(eng.step_times)
