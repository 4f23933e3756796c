"""Observability-layer tests: telemetry must be a pure observer.

The load-bearing invariants: (1) greedy token streams are BIT-IDENTICAL
with telemetry on vs off, at every pipeline depth, on both cache layouts
— instrumentation may never perturb the serving path; (2) request-level
metrics (submitted/finished/tokens, per-request event multiset) are
invariant across pipeline depths and mesh shapes — depth changes WHEN
host bookkeeping runs, never WHAT it observes; (3) the disabled path is a
pinned no-op (shared NULL_TELEMETRY singleton, one reused nullcontext
span); (4) the exports are well-formed (Prometheus 0.0.4 text, loadable
Chrome trace, JSONL) and the ring buffer is bounded with an honest
dropped count."""

import json
import urllib.error
import urllib.request
from collections import Counter as MultiSet

import jax
import numpy as np
import pytest

from repro.configs.paper_models import small_lm
from repro.models import build_model
from repro.obs import (
    NULL_TELEMETRY,
    EventTracer,
    MetricsRegistry,
    MetricsServer,
    Telemetry,
    disabled,
)
from repro.serving.engine import ServingEngine
from repro.serving.spec import SpecConfig

VOCAB = 256


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = small_lm(name="tiny-obs", vocab_size=VOCAB, num_layers=2,
                   d_model=64, d_ff=96, num_heads=4)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return model, params


@pytest.fixture(scope="module")
def draft_params(tiny_lm):
    _, params = tiny_lm
    k = jax.random.key(99)
    return jax.tree.map(
        lambda x: x + 0.02 * jax.random.normal(k, x.shape, x.dtype)
        if x.ndim >= 2 else x,
        params,
    )


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(1)
    return [rng.integers(2, 200, size=n) for n in (6, 18, 7, 5)]


LENS = [9, 3, 6, 4]


def _serve(model, params, depth, prompts, lens, telemetry=None, **kw):
    eng = ServingEngine(model, params, max_batch=2, max_len=64, seed=0,
                        pipeline_depth=depth, telemetry=telemetry, **kw)
    uids = [eng.submit(p, max_new_tokens=m)
            for p, m in zip(prompts, lens)]
    out = eng.run()
    return [out[u] for u in uids], eng


def _counter_value(tel, name):
    fam = tel.metrics.snapshot()[name]
    return sum(s["value"] for s in fam["series"])


def _request_event_multiset(tel):
    """Per-request lifecycle events as a {(name, tid): n} multiset —
    depth- and mesh-invariant, unlike step events whose timing varies."""
    return MultiSet(
        (e.name, e.tid) for e in tel.tracer.events() if e.cat == "request"
        and e.name != "preempt_ready"
    )


# --------------------------------------------------- bit-identity pins


def _bench_observer(traced):
    """The benchmark harness's engine observer (bench/harness/loadgen.py):
    a subclass of the no-op telemetry that records host timestamps."""
    import importlib
    import sys
    from pathlib import Path

    bench = str(Path(__file__).resolve().parents[1] / "bench")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    return importlib.import_module("harness.loadgen").make_observer(traced)


class TestBitIdentity:
    @pytest.mark.parametrize("paged", [True, False])
    def test_greedy_streams_identical_with_telemetry(self, tiny_lm, prompts,
                                                     paged):
        model, params = tiny_lm
        base, _ = _serve(model, params, 1, prompts, LENS, paged=paged)
        for depth in (1, 2, 4):
            got, eng = _serve(model, params, depth, prompts, LENS,
                              telemetry=Telemetry(), paged=paged)
            assert got == base, f"depth={depth} paged={paged}"
            assert eng.obs.enabled
        for traced in (False, True):
            got, eng = _serve(model, params, 2, prompts, LENS,
                              telemetry=_bench_observer(traced), paged=paged)
            assert got == base, f"observer traced={traced} paged={paged}"
            assert sum(eng.obs.count.values()) == sum(LENS)

    def test_spec_streams_identical_with_telemetry(self, tiny_lm,
                                                   draft_params, prompts):
        model, params = tiny_lm
        sc = lambda: SpecConfig(draft_params=draft_params, k=3,  # noqa: E731
                                draft_ratio=0.6)
        base, _ = _serve(model, params, 1, prompts, LENS, paged=True,
                         spec_config=sc())
        got, eng = _serve(model, params, 2, prompts, LENS, paged=True,
                          spec_config=sc(), telemetry=Telemetry())
        assert got == base
        assert eng.obs.spec_meta == {"k": 3, "draft_ratio": 0.6}


# ------------------------------------------ depth / mesh invariance


class TestInvariance:
    @pytest.mark.parametrize("paged", [True, False])
    def test_request_metrics_invariant_across_depths(self, tiny_lm, prompts,
                                                     paged):
        model, params = tiny_lm
        snaps = {}
        for depth in (1, 2, 4):
            _, eng = _serve(model, params, depth, prompts, LENS,
                            telemetry=Telemetry(), paged=paged)
            tel = eng.obs
            snaps[depth] = {
                "submitted": _counter_value(
                    tel, "serving_requests_submitted_total"),
                "finished": _counter_value(
                    tel, "serving_requests_finished_total"),
                "tokens": _counter_value(
                    tel, "serving_tokens_emitted_total"),
                "events": _request_event_multiset(tel),
            }
        assert snaps[1] == snaps[2] == snaps[4]
        assert snaps[1]["submitted"] == len(prompts)
        assert snaps[1]["finished"] == len(prompts)
        assert snaps[1]["tokens"] == sum(LENS)

    @pytest.mark.skipif(jax.device_count() < 4,
                        reason="needs 4 (emulated) devices")
    def test_request_metrics_invariant_across_mesh(self, tiny_lm, prompts):
        from repro.launch.mesh import make_serving_mesh
        from repro.parallel.sharding import make_parallelism

        model, params = tiny_lm
        results = {}
        for dp, tp in ((1, 1), (2, 2)):
            par = (make_parallelism(make_serving_mesh(dp, tp))
                   if dp * tp > 1 else None)
            toks, eng = _serve(model, params, 2, prompts, LENS,
                               telemetry=Telemetry(), paged=True,
                               parallelism=par)
            tel = eng.obs
            results[(dp, tp)] = (toks, _request_event_multiset(tel),
                                 _counter_value(
                                     tel, "serving_tokens_emitted_total"))
        assert results[(1, 1)] == results[(2, 2)]


# --------------------------------------------------- disabled no-op pin


class TestDisabledPath:
    def test_engine_default_is_null_singleton(self, tiny_lm):
        model, params = tiny_lm
        eng = ServingEngine(model, params, max_batch=2, max_len=64)
        assert eng.obs is NULL_TELEMETRY
        assert not eng.obs.enabled
        assert disabled() is NULL_TELEMETRY

    def test_null_span_is_one_reused_nullcontext(self):
        a = NULL_TELEMETRY.span("x")
        b = NULL_TELEMETRY.span("y")
        assert a is b  # no per-call allocation on the disabled hot path
        with a:
            pass

    def test_null_hooks_are_stateless_noops(self):
        NULL_TELEMETRY.on_submit(0, 1, 2)
        NULL_TELEMETRY.on_step_dispatch("decode", 1, 2, 0.1)
        NULL_TELEMETRY.on_spec_row(4, 2)
        NULL_TELEMETRY.on_step_consume("decode", 1e-3, 1e-4, 2e-3, True)
        NULL_TELEMETRY.on_prefill_tick(2, 30, 128, 1e-3, 2)
        assert NULL_TELEMETRY.snapshot() == {}
        assert not hasattr(NULL_TELEMETRY, "__dict__")  # __slots__ pin


# -------------------------------------------------- event stream shape


class TestEventStream:
    def test_lifecycle_ordering_per_request(self, tiny_lm, prompts):
        model, params = tiny_lm
        _, eng = _serve(model, params, 2, prompts, LENS,
                        telemetry=Telemetry(), paged=True)
        by_uid = {}
        for e in eng.obs.tracer.events():
            if e.cat == "request":
                by_uid.setdefault(e.tid, []).append(e.name)
        assert set(by_uid) == set(range(len(prompts)))
        order = {"submit": 0, "admit": 1, "first_chunk": 2,
                 "first_token": 3, "commit": 4, "finish": 5}
        for uid, names in by_uid.items():
            assert names[0] == "submit" and names[-1] == "finish"
            # submit < admit < first_chunk < first_token <= commits < finish
            ranks = [order[n] for n in names if n != "commit"]
            assert ranks == sorted(ranks), f"uid={uid}: {names}"

    def test_timestamps_monotone_within_request(self, tiny_lm, prompts):
        model, params = tiny_lm
        _, eng = _serve(model, params, 1, prompts, LENS,
                        telemetry=Telemetry())
        by_uid = {}
        for e in eng.obs.tracer.events():
            if e.cat == "request":
                by_uid.setdefault(e.tid, []).append(e.ts_us)
        for uid, ts in by_uid.items():
            assert ts == sorted(ts), f"uid={uid}"

    def test_ring_buffer_bound_and_dropped_count(self):
        tr = EventTracer(capacity=8)
        for i in range(20):
            tr.instant(f"e{i}", "step", 0, 0)
        assert len(tr) == 8
        assert tr.dropped == 12
        assert [e.name for e in tr.events()] == [f"e{i}" for i in
                                                 range(12, 20)]
        ct = tr.chrome_trace()
        assert ct["otherData"]["dropped_events"] == 12

    def test_chrome_trace_loadable(self, tiny_lm, prompts, tmp_path):
        model, params = tiny_lm
        _, eng = _serve(model, params, 2, prompts, LENS,
                        telemetry=Telemetry(), paged=True)
        p = tmp_path / "trace.json"
        eng.obs.tracer.export_chrome(str(p))
        doc = json.loads(p.read_text())
        evs = doc["traceEvents"]
        assert any(e["ph"] == "M" and e["name"] == "process_name"
                   for e in evs)
        for e in evs:
            if e["ph"] == "X":
                assert e["dur"] >= 0 and "ts" in e
            if e["ph"] == "i":
                assert e["s"] == "t"
        names = {e["name"] for e in evs}
        assert {"submit", "finish", "dispatch:decode",
                "sync:decode"} <= names

    def test_jsonl_export_round_trips(self, tiny_lm, prompts, tmp_path):
        model, params = tiny_lm
        _, eng = _serve(model, params, 1, prompts, LENS,
                        telemetry=Telemetry())
        p = tmp_path / "trace.jsonl"
        eng.obs.tracer.export_jsonl(str(p))
        lines = [json.loads(ln) for ln in p.read_text().splitlines()]
        assert len(lines) == len(eng.obs.tracer)
        assert all("name" in ln and "ts" in ln for ln in lines)

    def test_chrome_trace_on_empty_ring(self, tmp_path):
        """A tracer that never recorded must still export a loadable
        trace: just the process-name metadata, honest zero counts."""
        tr = EventTracer(capacity=4)
        doc = tr.chrome_trace()
        assert all(e["ph"] == "M" for e in doc["traceEvents"])
        assert doc["otherData"] == {"dropped_events": 0, "total_events": 0}
        p = tmp_path / "empty.json"
        tr.export_chrome(str(p))
        assert json.loads(p.read_text())["traceEvents"]
        p2 = tmp_path / "empty.jsonl"
        tr.export_jsonl(str(p2))
        assert p2.read_text() == ""

    def test_instant_timestamps_monotone(self):
        """Auto-stamped instants never go backwards, and an explicit
        ts_us override lands verbatim (the engine backdates nothing)."""
        tr = EventTracer()
        for i in range(50):
            tr.instant(f"e{i}", "step")
        ts = [e.ts_us for e in tr.events()]
        assert ts == sorted(ts)
        assert all(t >= 0.0 for t in ts)
        tr.instant("pinned", "step", ts_us=123.5)
        assert tr.events()[-1].ts_us == 123.5

    def test_chrome_events_carry_required_keys(self):
        """Perfetto's legacy loader needs name/ph/ts/pid/tid on every
        event, dur on X (complete) and a scope on i (instant)."""
        tr = EventTracer()
        tr.instant("inst", "cat", pid=1, tid=7, args={"k": 1})
        tr.complete("span", "cat", dur_s=0.002, pid=0, tid=3)
        evs = [e.to_chrome() for e in tr.events()]
        for e in evs:
            assert {"name", "cat", "ph", "ts", "pid", "tid"} <= e.keys()
        inst = next(e for e in evs if e["ph"] == "i")
        assert inst["s"] == "t" and "dur" not in inst
        assert inst["args"] == {"k": 1}
        span = next(e for e in evs if e["ph"] == "X")
        assert span["dur"] == pytest.approx(2000.0)
        # complete() backdates the start by dur: end = ts + dur is "now"
        assert span["ts"] + span["dur"] >= inst["ts"]
        assert "s" not in span


# ----------------------------------------------------- metrics registry


class TestMetrics:
    def test_histogram_percentiles_and_buckets(self):
        reg = MetricsRegistry()
        h = reg.histogram("h_test", "t", buckets=(1.0, 2.0, 4.0))
        for v in (0.5, 1.5, 3.0, 8.0):
            h.observe(v)
        assert h.count == 4 and h.max == 8.0
        assert h.percentile(50) == pytest.approx(1.5, abs=1.6)
        snap = h.snapshot()
        assert snap["buckets"]["4.0"] == 3  # cumulative <= 4.0
        assert snap["count"] == 4  # overflow sample still counted
        h.percentile(101)  # out-of-range q clamps, never raises

    def test_empty_histogram_is_zero_not_nan(self):
        reg = MetricsRegistry()
        h = reg.histogram("h_empty", "t", buckets=(1.0,))
        assert h.percentile(50) == 0.0
        assert h.mean() == 0.0

    def test_reregistration_must_match(self):
        reg = MetricsRegistry()
        reg.counter("c1", "x")
        assert reg.counter("c1", "x") is reg.counter("c1", "x")
        with pytest.raises(ValueError):
            reg.gauge("c1", "x")

    def test_prometheus_text_exposition(self, tiny_lm, prompts):
        model, params = tiny_lm
        _, eng = _serve(model, params, 2, prompts, LENS,
                        telemetry=Telemetry(), paged=True)
        txt = eng.obs.metrics.prometheus_text()
        assert "# TYPE serving_requests_submitted_total counter" in txt
        assert "# TYPE serving_ttft_seconds histogram" in txt
        assert 'le="+Inf"' in txt
        assert 'serving_pool_blocks_in_use{shard="0"}' in txt
        for line in txt.splitlines():
            if line and not line.startswith("#"):
                assert len(line.rsplit(" ", 1)) == 2

    def test_metrics_server_http_smoke(self):
        reg = MetricsRegistry()
        reg.counter("smoke_total", "x").inc(3)
        srv = MetricsServer(reg, port=0)
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/metrics") as r:
                body = r.read().decode()
                assert "smoke_total 3" in body
                assert r.headers["Content-Type"].startswith("text/plain")
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/metrics.json") as r:
                doc = json.loads(r.read())
                assert doc["smoke_total"]["series"][0]["value"] == 3
        finally:
            srv.close()

    def test_metrics_server_healthz_and_shutdown(self):
        """/healthz answers while the server lives; close() releases the
        port (a daemon thread must not linger holding the socket)."""
        reg = MetricsRegistry()
        srv = MetricsServer(reg, port=0)
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/healthz") as r:
                assert r.status == 200
                assert r.read() == b"ok\n"
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/nope")
        finally:
            srv.close()
        with pytest.raises(urllib.error.URLError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/healthz", timeout=2)


# ---------------------------------------------- engine-side accounting


class TestEngineAccounting:
    def test_empty_stats_fully_keyed(self, tiny_lm):
        model, params = tiny_lm
        eng = ServingEngine(model, params, max_batch=2, max_len=64)
        s = eng.stats()
        assert s["steps"] == 0
        for key in ("step_mean_s", "step_p50_s", "step_p90_s",
                    "step_p99_s", "device_wait_mean_s",
                    "device_wait_p50_s", "host_mean_s", "host_p50_s"):
            assert s[key] == 0.0
        assert s["pipeline_depth"] >= 1 and s["live_rows"] == 0
        assert eng.telemetry_snapshot() == {}

    def test_empty_spec_stats_division_safe(self, tiny_lm, draft_params):
        model, params = tiny_lm
        eng = ServingEngine(
            model, params, max_batch=2, max_len=64, paged=True,
            spec_config=SpecConfig(draft_params=draft_params, k=3))
        ss = eng.spec_stats()
        assert ss["proposed"] == 0 and ss["acceptance_rate"] == 0.0
        assert ss["committed_per_row_step"] == 0.0
        assert np.isfinite(list(
            v for v in ss.values() if isinstance(v, float))).all()

    def test_allocator_lifetime_counters(self, tiny_lm, prompts):
        model, params = tiny_lm
        _, eng = _serve(model, params, 2, prompts, LENS,
                        telemetry=Telemetry(), paged=True)
        c = eng.kv.alloc.counters
        assert c["alloc_calls"] > 0 and c["alloc_blocks"] > 0
        assert c["freed_blocks"] == c["alloc_blocks"]  # all requests done
        snap = eng.telemetry_snapshot()
        assert snap["engine"]["allocator"] == c

    def test_spec_outcome_accounting_matches_engine(self, tiny_lm,
                                                    draft_params, prompts):
        model, params = tiny_lm
        _, eng = _serve(
            model, params, 2, prompts, LENS, paged=True,
            telemetry=Telemetry(),
            spec_config=SpecConfig(draft_params=draft_params, k=3,
                                   draft_ratio=0.6))
        tel = eng.obs
        block = tel.bench_block()
        spec = block["spec"]
        assert spec is not None
        assert spec["k"] == 3 and spec["draft_ratio"] == 0.6
        assert spec["row_steps"] == eng.spec_step_rows
        accepted = sum(o["accepted"] * o["rows"] for o in spec["outcomes"])
        proposed = sum(o["k"] * o["rows"] for o in spec["outcomes"])
        assert accepted == eng.spec_accepted
        assert proposed == eng.spec_proposed
        assert spec["acceptance_rate"] == pytest.approx(
            eng.spec_stats()["acceptance_rate"])

    def test_bench_block_shape(self, tiny_lm, prompts):
        model, params = tiny_lm
        _, eng = _serve(model, params, 2, prompts, LENS,
                        telemetry=Telemetry(), paged=True)
        bb = eng.obs.bench_block()
        assert bb["ttft_s"]["count"] == len(prompts)
        assert bb["tokens"] == sum(LENS)
        assert bb["steps"] > 0
        assert 0 < bb["occupancy"]["rows_peak"] <= 2
        assert 0.0 < bb["occupancy"]["pool_frac_peak"] <= 1.0
        assert bb["spec"] is None
        json.dumps(bb)  # must be JSON-serializable as-is

    def test_preempt_ready_fires_under_pool_pressure(self, tiny_lm):
        model, params = tiny_lm
        rng = np.random.default_rng(3)
        tel = Telemetry()
        # A pool sized for ~one long row forces FIFO backpressure while a
        # row is live -> the engine flags the fattest live row.
        eng = ServingEngine(model, params, max_batch=2, max_len=64,
                            paged=True, num_blocks=4, block_size=16,
                            telemetry=tel)
        for _ in range(3):
            eng.submit(rng.integers(2, 200, size=12), max_new_tokens=30)
        eng.run()
        assert tel.preempt_ready.value >= 1
        assert any(e.name == "preempt_ready"
                   for e in tel.tracer.events())


# ------------------------------------------------ instrumented roots


class TestInstrumentedRoots:
    def test_registry_roots_carry_obs_marker(self, tiny_lm):
        from repro.launch.steps import RootContext, serving_root_registry

        model, _ = tiny_lm
        ctx = RootContext(model=model, max_batch=2, max_len=64)
        seen = []
        for layout in ("dense", "paged"):
            for spec in serving_root_registry(layout, spec=True):
                fn = spec.build(ctx)
                assert hasattr(fn, "__obs_name__"), (layout, spec.name)
                seen.append(fn.__obs_name__)
        assert "paged_decode" in seen and "decode" in seen

    def test_profile_capture_writes_trace(self, tiny_lm, prompts, tmp_path):
        model, params = tiny_lm
        prof_dir = tmp_path / "prof"
        tel = Telemetry(profile_dir=str(prof_dir), profile_steps=2)
        _serve(model, params, 1, prompts, LENS, telemetry=tel)
        if tel.profile is not None:
            tel.profile.stop()
        files = list(prof_dir.rglob("*")) if prof_dir.exists() else []
        assert any(f.is_file() for f in files), "no profiler artifacts"

    def test_profile_capture_raises_when_trace_cannot_start(self, tmp_path):
        """A requested profile that cannot start (here: another trace is
        already running) raises instead of being silently dropped."""
        from repro.obs import ProfileCapture

        jax.profiler.start_trace(str(tmp_path / "outer"))
        try:
            cap = ProfileCapture(str(tmp_path / "inner"), n_steps=1)
            with pytest.raises(RuntimeError, match="already been started"):
                cap.tick_dispatch()
            assert not cap.started
        finally:
            jax.profiler.stop_trace()
