"""Host spans and counters of the serving engine loop (repro.obs).

Every host phase of ``ServingEngine.run`` is a span that nests inside the
phase that calls it; each chunked-prefill tick reports its rows, prompt
tokens and token slots; each consumed step reports its wall time and
whether a chunk tick was queued on the device ahead of it; and the event
tracer's spans share the profiler's clock, so its Chrome export and the
``.xplane.pb`` show the same phases at the same times."""

import contextlib
import glob

import jax
import numpy as np
import pytest

from repro.configs.paper_models import small_lm
from repro.models import build_model
from repro.obs import Telemetry
from repro.obs.trace import TID_SPANS
from repro.serving.engine import ServingEngine

MAX_BATCH, CHUNK = 2, 8
PROMPT_LENS = (20, 13)  # three chunks and two chunks

# Each span of the engine loop, and the spans it may be opened inside
# (None: directly inside run()).
PARENTS = {
    "serving.admit": {None},
    "serving.drain": {None, "serving.admit", "serving.grow"},
    "serving.ring_sync": {None, "serving.drain"},
    "serving.commit": {None, "serving.drain"},
    "serving.prefill_tick": {"serving.admit"},
    "serving.prefill_tick.warm": {"serving.prefill_tick"},
    "serving.prefill_tick.build": {"serving.prefill_tick"},
    "serving.request_keys": {"serving.prefill_tick.build"},
    "serving.prefill_tick.dispatch": {"serving.prefill_tick"},
    "serving.prefill_tick.first_sync": {"serving.prefill_tick"},
    "serving.prefill_tick.emit": {"serving.prefill_tick"},
    "serving.grow": {None},
    "serving.dispatch.decode": {None},
}


class Recorder(Telemetry):
    """Telemetry that also keeps every span with the span it opened in,
    and the arguments of the tick and consume hooks."""

    def __init__(self):
        super().__init__()
        self.stack = []
        self.spans = []      # (name, enclosing span or None)
        self.ticks = []      # (rows, tokens, slots, host_s, rung)
        self.consumes = []   # (kind, sync_s, host_s, step_s, tick_ahead)

    @contextlib.contextmanager
    def span(self, name):
        self.spans.append((name, self.stack[-1] if self.stack else None))
        self.stack.append(name)
        with super().span(name):
            yield
        assert self.stack.pop() == name

    def on_prefill_tick(self, rows, tokens, slots, host_s, rung):
        super().on_prefill_tick(rows, tokens, slots, host_s, rung)
        self.ticks.append((rows, tokens, slots, host_s, rung))

    def on_step_consume(self, kind, sync_s, host_s, step_s, tick_ahead):
        super().on_step_consume(kind, sync_s, host_s, step_s, tick_ahead)
        self.consumes.append((kind, sync_s, host_s, step_s, tick_ahead))


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = small_lm(name="tiny-spans", vocab_size=128, num_layers=2,
                   d_model=32, d_ff=64, num_heads=4)
    model = build_model(cfg)
    return model, model.init(jax.random.key(0))


def _engine(tiny_lm, telemetry):
    model, params = tiny_lm
    return ServingEngine(model, params, max_batch=MAX_BATCH, max_len=64,
                         seed=0, paged=True, block_size=8,
                         prefill_chunk=CHUNK, pipeline_depth=2,
                         telemetry=telemetry)


def _prompt(rng, n):
    return rng.integers(2, 120, size=n)


@pytest.fixture(scope="module")
def served(tiny_lm):
    """Two prompts of two and three chunks through a recording engine."""
    rec = Recorder()
    eng = _engine(tiny_lm, rec)
    rng = np.random.default_rng(3)
    for n in PROMPT_LENS:
        eng.submit(_prompt(rng, n), max_new_tokens=6)
    eng.run()
    return eng, rec


def test_every_engine_loop_span_nests_inside_its_parent(served):
    _, rec = served
    assert rec.stack == []
    seen = {name for name, _ in rec.spans}
    assert set(PARENTS) <= seen
    for name, parent in rec.spans:
        if name in PARENTS:
            assert parent in PARENTS[name], (name, parent)
    assert ("serving.ring_sync", "serving.drain") in rec.spans
    assert ("serving.drain", "serving.admit") in rec.spans


def test_spans_are_chrome_events_on_the_span_lane(served):
    _, rec = served
    doc = rec.tracer.chrome_trace()
    lane = [e for e in doc["traceEvents"]
            if e.get("ph") == "X" and e.get("tid") == TID_SPANS]
    assert {e["name"] for e in lane} == {name for name, _ in rec.spans}
    assert all(e["cat"] == "span" and e["dur"] >= 0 for e in lane)


def test_prefill_ticks_count_prompt_tokens_and_slots(served):
    _, rec = served
    assert sum(t for _, t, _, _, _ in rec.ticks) == sum(PROMPT_LENS)
    # MAX_BATCH 2 has the one rung 2.
    assert all(s == MAX_BATCH * CHUNK for _, _, s, _, _ in rec.ticks)
    assert all(1 <= r <= MAX_BATCH and h >= 0
               for r, _, _, h, _ in rec.ticks)
    # Both prompts stream together: three ticks, the last with one row.
    assert [r for r, _, _, _, _ in rec.ticks] == [2, 2, 1]
    snap = rec.metrics.snapshot()
    assert snap["serving_prefill_ticks_total"]["series"][0]["value"] == 3
    fill = snap["serving_prefill_tick_fill_frac"]["series"][0]
    assert fill["count"] == 3


def test_prefill_ticks_count_by_rung(tiny_lm):
    """A tick computes rung x chunk token slots, at the smallest rung that
    holds its prompts, and counts under that rung's ``rows`` label.  The
    all-padding ticks that compile every rung before the first real one
    are left out of the count: only their one span shows them."""
    model, params = tiny_lm
    rec = Recorder()
    eng = ServingEngine(model, params, max_batch=8, max_len=64, seed=0,
                        paged=True, block_size=8, prefill_chunk=CHUNK,
                        telemetry=rec)
    assert eng._tick_rungs == (2, 8)
    rng = np.random.default_rng(5)
    eng.submit(_prompt(rng, 12), max_new_tokens=2)  # two 1-row ticks
    eng.run()
    for n in (20, 13, 9):  # rows 3, 3, 1
        eng.submit(_prompt(rng, n), max_new_tokens=2)
    eng.run()
    assert [(r, g) for r, _, _, _, g in rec.ticks] == [
        (1, 2), (1, 2), (3, 8), (3, 8), (1, 2)]
    assert all(s == g * CHUNK for _, _, s, _, g in rec.ticks)
    series = rec.metrics.snapshot()["serving_prefill_ticks_total"]["series"]
    assert {s["labels"]["rows"]: s["value"] for s in series} == {
        "2": 3, "8": 2}
    names = [name for name, _ in rec.spans]
    assert names.count("serving.prefill_tick.warm") == 1


def test_step_consume_matches_step_times(served):
    eng, rec = served
    assert len(rec.consumes) == len(eng.step_times) > 0
    assert [c[3] for c in rec.consumes] == eng.step_times
    assert [c[1] for c in rec.consumes] == eng.step_device_wait_s
    assert [c[2] for c in rec.consumes] == eng.step_host_s


def test_tick_ahead_marks_steps_queued_behind_a_chunk_tick(tiny_lm):
    rec = Recorder()
    eng = _engine(tiny_lm, rec)
    rng = np.random.default_rng(4)
    eng.submit(_prompt(rng, 5), max_new_tokens=16)
    for _ in range(5):  # one tick that syncs its first token, then decode
        eng.run(max_steps=1)
    assert len(rec.ticks) == 1 and eng.active.sum() == 1
    n0 = len(rec.consumes)
    assert n0 >= 3
    assert not any(c[4] for c in rec.consumes)  # pure decode
    eng.submit(_prompt(rng, 20), max_new_tokens=4)
    eng.run(max_steps=1)  # drain, a three-chunk prompt's first tick, decode
    assert len(rec.ticks) == 2
    assert eng._ring[-1].tick_ahead  # dispatched behind the unsynced tick
    eng.run(max_steps=1)  # admission drains that step
    assert rec.consumes[n0][4] is False
    assert rec.consumes[n0 + 1][4] is True
    eng.run()
    # The last tick syncs its first token: nothing is queued behind it.
    assert not eng._tick_unsynced
    assert not rec.consumes[-1][4]
    by_tick = rec.metrics.snapshot()["serving_step_sync_by_tick_seconds"]
    labels = {s["labels"]["tick_ahead"] for s in by_tick["series"]}
    assert labels == {"true", "false"}


def test_tracer_spans_share_the_profiler_clock(tmp_path):
    """A span's tracer event and its profiler annotation start within a
    millisecond of each other once the ``.xplane.pb`` offsets are put on
    the profile's start time."""
    from jax.profiler import ProfileData

    tel = Telemetry()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tel.span("serving.clock_probe"):
            jax.numpy.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (ev,) = [e for e in tel.tracer.events()
             if e.name == "serving.clock_probe"]
    path = sorted(glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True))[-1]
    pd = ProfileData.from_file(path)
    start = dict(next(p for p in pd.planes
                      if p.name == "Task Environment").stats)
    (xev,) = [e for p in pd.planes if p.name == "/host:CPU"
              for line in p.lines for e in line.events
              if e.name == "serving.clock_probe"]
    xplane_us = (start["profile_start_time"] + xev.start_ns) / 1e3
    assert abs(ev.ts_us - xplane_us) < 1e3
