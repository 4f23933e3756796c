"""Serving fault-tolerance tests: seeded FaultPlan injection across every
fault kind, with the acceptance bar that NON-TARGETED requests' greedy
streams stay bit-identical to the fault-free run (both cache layouts,
pipeline depths 1-2), targeted requests finish with a structured
error/retry, and the fault accounting (plan fired log vs engine
counters) reconciles exactly."""

import json
import time
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from repro.configs.paper_models import small_lm
from repro.models import build_model
from repro.serving.engine import ServingEngine
from repro.serving.faults import (
    FAULT_KINDS,
    FaultPlan,
    FaultPolicy,
    FaultSpec,
    ServingFault,
    ServingFaultHandler,
)
from repro.serving.scheduler import SchedulerConfig

VOCAB = 256


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = small_lm(name="tiny-faults", vocab_size=VOCAB, num_layers=2,
                   d_model=64, d_ff=96, num_heads=4)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return model, params


def _prompts(seed, n, lo=4, hi=10):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, 200, size=int(rng.integers(lo, hi)))
            for _ in range(n)]


def _run(model, params, prompts, max_new=12, faults=None, policy=None,
         **kw):
    """Run one engine over ``prompts``; returns (streams-in-order, eng)."""
    kw.setdefault("max_batch", 3)
    kw.setdefault("max_len", 64)
    eng = ServingEngine(model, params, faults=faults, fault_policy=policy,
                        **kw)
    uids = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    out = eng.run()
    return [out.get(u) for u in uids], eng


# --------------------------------------------------------- plan units


class TestFaultSpec:
    def test_rejects_unknown_kind(self):
        with pytest.raises(ValueError, match="unknown fault kind"):
            FaultSpec("meteor_strike")

    def test_poison_requires_uid(self):
        with pytest.raises(ValueError, match="uid"):
            FaultSpec("poison_logits")

    @pytest.mark.parametrize("kw", [{"step": -1}, {"delay_s": -0.1}])
    def test_rejects_negative(self, kw):
        with pytest.raises(ValueError):
            FaultSpec("straggler", **kw)

    def test_kind_table_covers_spec_kinds(self):
        assert set(FAULT_KINDS) == {"poison_logits", "alloc_fail",
                                    "swap_corrupt", "straggler",
                                    "draft_kill"}


class TestFaultPlan:
    def test_take_gates_on_step_and_fires_once(self):
        plan = FaultPlan([FaultSpec("alloc_fail", step=3)])
        assert plan.take("alloc_fail", 2) is None
        sp = plan.take("alloc_fail", 3)
        assert sp is not None and sp.step == 3
        assert plan.take("alloc_fail", 4) is None   # fire-once
        assert plan.counts() == {"alloc_fail": 1}
        assert plan.outstanding() == []

    def test_take_uid_matching(self):
        plan = FaultPlan([FaultSpec("swap_corrupt", uid=7)])
        # uid-targeted spec never fires for another uid or for no uid
        assert plan.take("swap_corrupt", 0, uid=3) is None
        assert plan.take("swap_corrupt", 0, uid=None) is None
        assert plan.take("swap_corrupt", 0, uid=7) is not None
        # untargeted spec matches any uid
        plan = FaultPlan([FaultSpec("swap_corrupt")])
        assert plan.take("swap_corrupt", 0, uid=123) is not None

    def test_outstanding_reports_unfired(self):
        plan = FaultPlan([FaultSpec("straggler", step=999),
                          FaultSpec("alloc_fail")])
        plan.take("alloc_fail", 0)
        out = plan.outstanding()
        assert len(out) == 1 and out[0].kind == "straggler"

    def test_json_round_trip(self, tmp_path):
        plan = FaultPlan([FaultSpec("poison_logits", step=2, uid=1),
                          FaultSpec("straggler", step=4, delay_s=0.5)])
        path = tmp_path / "plan.json"
        path.write_text(plan.to_json())
        back = FaultPlan.from_json(str(path))
        assert back.specs == plan.specs

    def test_from_json_accepts_sparse_specs(self, tmp_path):
        path = tmp_path / "plan.json"
        path.write_text(json.dumps(
            {"faults": [{"kind": "alloc_fail"},
                        {"kind": "straggler", "step": 3}]}))
        plan = FaultPlan.from_json(str(path))
        assert len(plan) == 2 and plan.specs[1].step == 3


class TestFaultPolicy:
    def test_backoff_is_capped_exponential(self):
        pol = FaultPolicy(max_retries=8, retry_backoff_steps=4,
                          retry_backoff_cap=64)
        assert [pol.backoff(a) for a in (1, 2, 3, 4, 5, 6)] == \
            [4, 8, 16, 32, 64, 64]

    @pytest.mark.parametrize("kw", [
        {"max_retries": -1},
        {"retry_backoff_steps": 0},
        {"retry_backoff_cap": 0},
    ])
    def test_rejects_bad_knobs(self, kw):
        with pytest.raises(ValueError):
            FaultPolicy(**kw)

    def test_handler_retries_then_quarantines(self):
        class _R:
            retries = 0

        h = ServingFaultHandler(FaultPolicy(max_retries=2))
        r = _R()
        assert h.disposition(r) == ("retry", 4)
        assert h.disposition(r) == ("retry", 8)
        assert h.disposition(r) == ("quarantine", 0)
        assert (h.retried, h.quarantined) == (2, 1)
        assert r.retries == 2


# ----------------------------------------------- poisoned-step isolation


class TestPoisonIsolation:
    @pytest.mark.parametrize("paged", [False, True])
    @pytest.mark.parametrize("depth", [1, 2])
    def test_quarantine_isolates_healthy_streams(self, tiny_lm, paged,
                                                 depth):
        """A poisoned request retires with finish_reason='error'; every
        other request's greedy stream is bit-identical to the fault-free
        run — both cache layouts, pipeline depths 1 and 2."""
        model, params = tiny_lm
        prompts = _prompts(20, 3)
        base, _ = _run(model, params, prompts, paged=paged,
                       pipeline_depth=depth)
        plan = FaultPlan([FaultSpec("poison_logits", step=2, uid=1)])
        got, eng = _run(model, params, prompts, paged=paged,
                        pipeline_depth=depth, faults=plan)
        assert eng.finished_requests[1].finish_reason == "error"
        for uid in (0, 2):
            assert got[uid] == base[uid], uid
            assert eng.finished_requests[uid].finish_reason == "stop"
        fs = eng.fault_stats()
        assert fs["injected"] == {"poison_logits": 1}
        assert fs["quarantined"] == 1 and fs["retried"] == 0
        assert plan.outstanding() == []

    def test_retry_recovers_full_stream(self, tiny_lm):
        """With a retry budget the poisoned request reprefills after a
        backoff park and its final stream matches the fault-free run."""
        model, params = tiny_lm
        prompts = _prompts(21, 3)
        base, _ = _run(model, params, prompts, paged=True)
        plan = FaultPlan([FaultSpec("poison_logits", step=2, uid=1)])
        got, eng = _run(model, params, prompts, paged=True, faults=plan,
                        policy=FaultPolicy(max_retries=2,
                                           retry_backoff_steps=2))
        assert got == base
        fs = eng.fault_stats()
        assert fs["retried"] == 1 and fs["quarantined"] == 0
        assert eng.finished_requests[1].finish_reason == "stop"
        assert eng.finished_requests[1].retries == 1


# ---------------------------------------------- allocator + swap faults


class TestAllocAndSwapFaults:
    def test_alloc_fail_is_absorbed(self, tiny_lm):
        """Failed reservations back off and retry; streams and finish
        reasons are unchanged."""
        model, params = tiny_lm
        prompts = _prompts(22, 4)
        kw = dict(paged=True, block_size=8, num_blocks=24)
        base, _ = _run(model, params, prompts, **kw)
        plan = FaultPlan([FaultSpec("alloc_fail", step=0),
                          FaultSpec("alloc_fail", step=2),
                          FaultSpec("alloc_fail", step=4)])
        got, eng = _run(model, params, prompts, faults=plan, **kw)
        assert got == base
        assert all(r.finish_reason == "stop"
                   for r in eng.finished_requests.values())
        assert eng.fault_stats()["injected"]["alloc_fail"] == 3

    def test_swap_corrupt_falls_back_to_reprefill(self, tiny_lm):
        """A corrupted swap payload fails its checksum at resume and the
        engine reprefills from host context instead of scattering the
        poisoned blocks back — streams still match the uncontended run."""
        model, params = tiny_lm
        prompts = _prompts(23, 5)
        kw = dict(paged=True, block_size=8, num_blocks=8, max_new=16,
                  sched_config=SchedulerConfig(admission="on_demand",
                                               preempt=True,
                                               resume="swap"))
        # Uncontended baseline: same workload, pool covers worst case.
        base, b_eng = _run(model, params, prompts, paged=True,
                           block_size=8, num_blocks=32, max_new=16)
        assert b_eng.scheduler_stats()["preempt_count"] == 0
        plan = FaultPlan([FaultSpec("swap_corrupt")])
        got, eng = _run(model, params, prompts, faults=plan, **kw)
        assert eng.scheduler_stats()["preempt_count"] > 0
        assert eng.fault_stats()["swap_fallbacks"] == 1
        assert eng.fault_stats()["injected"]["swap_corrupt"] == 1
        assert got == base


# ------------------------------------------- stragglers + hard timeouts


class TestStragglerAndTimeout:
    def test_straggler_flagged_without_timeout(self, tiny_lm):
        """The watchdog classifies against a median of >=8 clean steps, so
        the stall is injected late enough for that baseline to exist."""
        model, params = tiny_lm
        prompts = _prompts(24, 2)
        base, _ = _run(model, params, prompts, paged=True, max_new=16)
        plan = FaultPlan([FaultSpec("straggler", step=11, delay_s=0.5)])
        got, eng = _run(model, params, prompts, paged=True, max_new=16,
                        faults=plan)
        assert got == base
        fs = eng.fault_stats()
        assert fs["injected"]["straggler"] == 1
        assert fs["straggler_slow"] >= 1

    def test_step_timeout_raises_structured_fault(self, tiny_lm):
        """Exceeding the hard step budget raises ServingFault with a
        JSON-serializable engine snapshot.  The engine is warmed on its
        own first request so jit compilation (seconds on CPU) does not
        trip the budget before the injected stall does."""
        import dataclasses

        model, params = tiny_lm
        plan = FaultPlan([FaultSpec("straggler", step=20, delay_s=0.6)])
        eng = ServingEngine(
            model, params, max_batch=1, max_len=64, paged=True,
            faults=plan, fault_policy=FaultPolicy())
        eng.submit(_prompts(25, 1)[0], max_new_tokens=4)
        eng.run()                                       # warm: steps ~5
        # Arm the hard budget only once jit caches are hot, as a
        # deployment would — compile steps are expected-slow.
        eng._fault_policy = dataclasses.replace(
            eng._fault_policy, step_timeout_s=0.5)
        eng.submit(_prompts(26, 1)[0], max_new_tokens=32)
        with pytest.raises(ServingFault) as ei:
            eng.run()
        assert ei.value.kind == "step_timeout"
        snap = ei.value.snapshot
        assert snap["step"] >= 20 and snap["pipeline_depth"] >= 1
        json.dumps(snap)                                # post-mortem-able


# ------------------------------------- deadlines, cancel, drain, close


class TestDeadlinesAndLifecycle:
    def test_deadline_shed(self, tiny_lm):
        """A queued request whose deadline lapses before admission is
        shed with finish_reason='deadline'; survivors are unaffected."""
        model, params = tiny_lm
        prompts = _prompts(27, 2)
        base, _ = _run(model, params, [prompts[0]], max_batch=1,
                       max_new=8)
        eng = ServingEngine(model, params, max_batch=1, max_len=64,
                            fault_policy=FaultPolicy())
        u0 = eng.submit(prompts[0], max_new_tokens=8)
        u1 = eng.submit(prompts[1], max_new_tokens=8, deadline_s=1e-4)
        time.sleep(0.01)
        out = eng.run()
        assert out[u0] == base[0]
        assert u1 not in out or out[u1] == []
        assert eng.finished_requests[u1].finish_reason == "deadline"
        assert eng.fault_stats()["shed"] == 1

    def test_submit_rejects_bad_deadline(self, tiny_lm):
        model, params = tiny_lm
        eng = ServingEngine(model, params, max_batch=1, max_len=64)
        with pytest.raises(ValueError):
            eng.submit(np.arange(2, 6), deadline_s=0.0)

    def test_cancel_queued(self, tiny_lm):
        model, params = tiny_lm
        prompts = _prompts(28, 2)
        eng = ServingEngine(model, params, max_batch=1, max_len=64)
        u0 = eng.submit(prompts[0], max_new_tokens=8)
        u1 = eng.submit(prompts[1], max_new_tokens=8)
        assert eng.cancel(u1) is True
        assert eng.cancel(u1) is False            # already gone
        assert eng.cancel(999) is False           # unknown uid
        out = eng.run()
        assert out[u1] == []                      # reported, empty stream
        assert eng.finished_requests[u1].finish_reason == "cancelled"
        assert eng.fault_stats()["cancelled"] == 1
        assert len(out[u0]) == 8

    def test_request_drain_sheds_backlog(self, tiny_lm):
        model, params = tiny_lm
        prompts = _prompts(29, 3)
        eng = ServingEngine(model, params, max_batch=1, max_len=64)
        uids = [eng.submit(p, max_new_tokens=6) for p in prompts]
        eng.request_drain()
        out = eng.run()
        # Only already-admittable work proceeds; the backlog sheds (and
        # is still reported in the output map, with an empty stream).
        reasons = [eng.finished_requests[u].finish_reason for u in uids]
        assert reasons.count("shutdown") >= 1
        assert all(r in ("stop", "shutdown") for r in reasons)
        for u, r in zip(uids, reasons):
            assert (len(out[u]) > 0) == (r == "stop")

    def test_close_is_idempotent_and_blocks_submit(self, tiny_lm):
        model, params = tiny_lm
        eng = ServingEngine(model, params, max_batch=1, max_len=64)
        eng.submit(np.arange(2, 8), max_new_tokens=4)
        eng.close()
        eng.close()                               # idempotent
        with pytest.raises(RuntimeError, match="closed"):
            eng.submit(np.arange(2, 8))
        assert all(r.finish_reason == "shutdown"
                   for r in eng.finished_requests.values())


# --------------------------------------------- speculative degradation


class TestSpecDegradation:
    def _spec_cfg(self, params):
        from repro.serving.spec import SpecConfig
        return SpecConfig(draft_params=params, k=2)

    def test_draft_kill_degrades_then_reenables(self, tiny_lm):
        """A draft-path crash degrades to plain decode (speculation is
        lossless, so streams are unchanged) and re-enables after the
        cool-down."""
        model, params = tiny_lm
        prompts = _prompts(30, 2)
        base, _ = _run(model, params, prompts, max_new=16,
                       spec_config=self._spec_cfg(params))
        plan = FaultPlan([FaultSpec("draft_kill", step=2)])
        got, eng = _run(model, params, prompts, max_new=16,
                        spec_config=self._spec_cfg(params), faults=plan,
                        policy=FaultPolicy(draft_cooldown_steps=3))
        assert got == base
        fs = eng.fault_stats()
        assert fs["draft_kills"] == 1
        assert fs["draft_reenables"] == 1
        assert not eng.degraded_components()      # healthy again at exit

    def test_real_draft_root_error_propagates(self, tiny_lm):
        """Only the injected draft kill degrades: an exception raised by
        the draft root itself (say, a kernel that fails to compile) is a
        failure of the run, never a silent switch to plain decode."""
        model, params = tiny_lm
        eng = ServingEngine(model, params, max_batch=3, max_len=64,
                            spec_config=self._spec_cfg(params))

        def broken_root(*args):
            raise ValueError("draft root failed to compile")

        eng._spec_draft = broken_root
        for p in _prompts(32, 2):
            eng.submit(p, max_new_tokens=8)
        with pytest.raises(ValueError, match="failed to compile"):
            eng.run()
        assert eng.fault_stats()["draft_kills"] == 0

    def test_spec_poison_quarantines_target_only(self, tiny_lm):
        model, params = tiny_lm
        prompts = _prompts(31, 3)
        base, _ = _run(model, params, prompts, max_new=12,
                       spec_config=self._spec_cfg(params))
        plan = FaultPlan([FaultSpec("poison_logits", step=2, uid=0)])
        got, eng = _run(model, params, prompts, max_new=12,
                        spec_config=self._spec_cfg(params), faults=plan)
        assert eng.finished_requests[0].finish_reason == "error"
        assert got[1] == base[1] and got[2] == base[2]
        assert eng.fault_stats()["quarantined"] == 1


# ----------------------------------------------- accounting + health


class TestAccountingAndHealth:
    def test_fault_stats_reconcile_with_plan(self, tiny_lm):
        """Every injected fault is accounted for: the engine's injected
        block equals the plan's fired log, nothing is outstanding, and
        the degradation counters match what each kind must trigger."""
        model, params = tiny_lm
        prompts = _prompts(32, 3)
        plan = FaultPlan([
            FaultSpec("poison_logits", step=2, uid=1),
            FaultSpec("alloc_fail", step=1),
            FaultSpec("straggler", step=4, delay_s=0.05),
        ])
        _, eng = _run(model, params, prompts, paged=True, faults=plan)
        fs = eng.fault_stats()
        assert fs["injected"] == plan.counts()
        assert fs["injected_total"] == 3 == len(plan.fired_log)
        assert plan.outstanding() == []
        assert fs["quarantined"] == 1           # the poison
        assert fs["straggler_slow"] >= 0        # soft flag, no timeout

    def test_snapshot_and_health_when_healthy(self, tiny_lm):
        model, params = tiny_lm
        _, eng = _run(model, params, _prompts(33, 2))
        assert eng.degraded_components() == {}
        snap = eng.engine_snapshot()
        for key in ("step", "ring_depth", "pipeline_depth", "slots",
                    "queued", "parked", "prefilling", "degraded",
                    "faults"):
            assert key in snap, key
        json.dumps(snap)

    def test_healthz_degraded_answers_503(self):
        from repro.obs import MetricsRegistry
        from repro.obs.metrics import MetricsServer

        state = {"bad": {}}
        srv = MetricsServer(MetricsRegistry(), port=0,
                            health=lambda: state["bad"])
        try:
            url = f"http://{srv.host}:{srv.port}/healthz"
            assert urllib.request.urlopen(url).status == 200
            state["bad"] = {"draft": {"off_until_step": 9}}
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(url)
            assert ei.value.code == 503
            body = json.loads(ei.value.read())
            assert body["status"] == "degraded"
            assert "draft" in body["components"]
        finally:
            srv.close()
