"""Drive the program through one cell's traffic and time what a user sees.

The entry the window drives is the program's own: ``ServingEngine.submit``
plus ``ServingEngine.run(max_steps=1)``, called in a loop.  Timestamps come
from an observer passed as the engine's ``telemetry=``: a subclass of the
program's no-op telemetry that overrides only the hooks it reads, so a hook
added to the program later stays a no-op here.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np

from . import traffic as traffic_mod


def _null_telemetry_type():
    from repro.obs import NULL_TELEMETRY

    return type(NULL_TELEMETRY)


def make_observer(traced: bool):
    """An engine telemetry object that records, on the host clock, each
    admission, first token, commit and decode dispatch; in a traced run
    its ``span`` also writes the engine's own host spans into the trace."""

    class Observer(_null_telemetry_type()):
        enabled = True

        def __init__(self):
            self.admits: List[tuple] = []       # (t, uid, wait_s)
            self.tokens: Dict[int, List[tuple]] = collections.defaultdict(
                list)                            # uid -> [(t, n)]
            self.dispatches: List[tuple] = []   # (t, live_rows, live_tok)
            self.expect: Dict[int, int] = {}    # uid -> tokens it asked for
            self.count: Dict[int, int] = collections.Counter()
            self.completed: List[int] = []      # uids, in completion order

        def _got(self, uid, n):
            self.tokens[uid].append((time.perf_counter(), n))
            self.count[uid] += n
            if self.count[uid] == self.expect.get(uid):
                self.completed.append(uid)

        def on_admit(self, uid, slot, wait_s):
            self.admits.append((time.perf_counter(), uid, wait_s))

        def on_first_token(self, uid, slot, ttft_s):
            self._got(uid, 1)

        def on_commit(self, uid, slot, n_tokens):
            self._got(uid, n_tokens)

        def on_step_dispatch(self, kind, ring_depth, live_rows, dispatch_s,
                             pool_in_use=None, blocks_per_shard=None,
                             live_tokens=None, reserved_tokens=None):
            self.dispatches.append((time.perf_counter(), live_rows,
                                    live_tokens or 0))

        if traced:
            def span(self, name):
                import jax

                return jax.profiler.TraceAnnotation(name)

    return Observer()


@dataclasses.dataclass
class Sent:
    """One request as the harness sent it."""
    req: traffic_mod.Request
    uid: int
    due: float          # when it was due (open loop) or sent (closed loop)
    sent: float         # when the harness called submit()


@dataclasses.dataclass
class Window:
    start: float
    end: float
    sent: List[Sent]
    obs: object
    step_times: list            # engine per-step wall times in the window
    step_host_s: list           # engine per-step host bookkeeping

    @property
    def seconds(self) -> float:
        return self.end - self.start


class LoadGen:
    """Open- or closed-loop traffic against one engine.  Requests are greedy
    with no EOS id, so each ends at its drawn output length."""

    def __init__(self, engine, mix: dict, requests, traced: bool = False):
        self.eng = engine
        self.mix = mix
        self.requests = list(requests)
        self.next = 0
        self.sent: List[Sent] = []
        self.outstanding = 0
        self.idle_clients = 0
        self.seen = 0
        self.traced = traced

    def _span(self, name: str):
        if not self.traced:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def _request(self, i: int) -> traffic_mod.Request:
        """The i-th request; the drawn set repeats if a run outlasts it."""
        return self.requests[i % len(self.requests)]

    def _submit(self, due: float) -> None:
        r = self._request(self.next)
        self.next += 1
        t_call = time.perf_counter()
        with self._span("bench.submit"):
            uid = self.eng.submit(r.prompt, max_new_tokens=r.max_new)
        self.eng.obs.expect[uid] = r.max_new
        self.sent.append(Sent(r, uid, due, t_call))
        self.outstanding += 1

    def _collect_finished(self) -> int:
        """Requests whose last token reached the harness since last call."""
        new = len(self.eng.obs.completed) - self.seen
        self.seen += new
        self.outstanding -= new
        return new

    def run_until(self, t_end: float, t0: float, on_tick=None) -> None:
        """Offer traffic and drive the engine until ``t_end``."""
        open_loop = self.mix["loop"] == "open"
        if open_loop and not self.sent and self.next == 0:
            self.due = t0 + self._request(0).gap_s
        if not open_loop and not self.sent:
            self.idle_clients = self.mix["clients"]
        while True:
            now = time.perf_counter()
            if on_tick is not None:
                on_tick(now)
            if now >= t_end:
                return
            if open_loop:
                while self.due <= now:
                    self._submit(self.due)
                    self.due += self._request(self.next).gap_s
            else:
                self.idle_clients += self._collect_finished()
                while self.idle_clients:
                    self._submit(time.perf_counter())
                    self.idle_clients -= 1
            if open_loop:
                self._collect_finished()
            if self.outstanding == 0:
                # Nothing to serve: wait for the next arrival.
                wake = min(self.due if open_loop else t_end, t_end)
                time.sleep(max(0.0, min(wake - time.perf_counter(), 0.002)))
                continue
            with self._span("bench.run1"):
                self.eng.run(max_steps=1)


TRACE_LEAD_S = 5.0   # the profiler starts this long before its window


def drive(engine, mix: dict, requests, seconds: float, traced: bool,
          trace_dir: Optional[str], setup_clock) -> Window:
    """Lead-in, then the measured window; returns what the window saw.
    ``setup_clock(t)`` is called with the time the window opens.

    A traced run starts the profiler inside the lead-in, so that the stall
    of starting it ends before the window; its window is the first
    ``trace_s`` seconds, marked ``bench.window`` in the trace, and the run
    ends when the profiler has stopped, since stopping stalls the host
    while the trace is written."""
    import jax

    drv = LoadGen(engine, mix, requests, traced=traced)
    t0 = time.perf_counter()
    lead_end = t0 + mix["lead_in_s"]
    if traced:
        drv.run_until(lead_end - TRACE_LEAD_S, t0)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    drv.run_until(lead_end, t0)
    n_steps0 = len(engine.step_times)
    w_start = time.perf_counter()
    setup_clock(w_start)
    w_end = w_start + (min(seconds, mix["trace_s"]) if traced else seconds)
    if traced:
        with jax.profiler.TraceAnnotation("bench.window"):
            drv.run_until(w_end, t0)
    else:
        drv.run_until(w_end, t0)
    n_steps1 = len(engine.step_times)
    w_end = time.perf_counter()
    if traced:
        jax.profiler.stop_trace()
    return Window(
        start=w_start, end=w_end, sent=drv.sent, obs=engine.obs,
        step_times=list(engine.step_times[n_steps0:n_steps1]),
        step_host_s=list(engine.step_host_s[n_steps0:n_steps1]))


# ------------------------------------------------------------ reductions


def token_times(win: Window, uid: int) -> List[tuple]:
    """[(arrival time, tokens)] of one request, in order."""
    return sorted(win.obs.tokens.get(uid, []))


def ttft_samples(win: Window) -> np.ndarray:
    """Seconds from due (open) or sent (closed) to the first token, for
    every request whose first token reached the harness in the window."""
    out = []
    for s in win.sent:
        toks = token_times(win, s.uid)
        if toks and win.start <= toks[0][0] < win.end:
            out.append(toks[0][0] - s.due)
    return np.asarray(out)


def itl_samples(win: Window) -> np.ndarray:
    """Every gap between consecutive tokens of one request that ends in the
    window; a commit of n tokens counts as n gaps of 1/n its interval."""
    out = []
    for s in win.sent:
        toks = token_times(win, s.uid)
        for (ta, _), (tb, n) in zip(toks, toks[1:]):
            if win.start <= tb < win.end:
                out.extend([(tb - ta) / n] * n)
    return np.asarray(out)


def tokens_in_window(win: Window) -> int:
    return sum(n for s in win.sent for t, n in token_times(win, s.uid)
               if win.start <= t < win.end)


def finished(win: Window) -> List[Sent]:
    """Requests whose every token reached the harness by the window's end."""
    out = []
    for s in win.sent:
        toks = token_times(win, s.uid)
        if (sum(n for _, n in toks) >= s.req.max_new
                and toks[-1][0] < win.end):
            out.append(s)
    return out
