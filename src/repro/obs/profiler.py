"""jax.profiler hooks for the serving hot path.

Two instrumentation layers, split by where they run:

  * ``wrap_root(fn, name)`` — wraps a serving root's step function in a
    ``jax.named_scope`` so every op the root lowers carries the root's
    name in profiler timelines and HLO dumps.  named_scope is pure
    metadata (it annotates the jaxpr, it emits no ops), so the wrapped
    root lowers to the same computation — the static contract auditor
    traces the WRAPPED builds (launch/steps.serving_root_registry wraps at
    the registry, the auditor's single source of truth), which is the
    proof the instrumentation adds zero transfers.  Applied
    unconditionally: there is no on/off divergence to perturb tokens.

  * ``annotation(name)`` — a host-side ``jax.profiler.TraceAnnotation``
    span for the host phases of the ENGINE loop (``serving.*``, outside
    jit).  These only mark time on the host timeline while a profiler
    trace is being captured; they never touch the computation.

``ProfileCapture`` drives ``jax.profiler.start_trace/stop_trace`` from the
engine's step hooks: capture begins at the first dispatched step and ends
after N steps have been consumed (so the captured window holds N complete
dispatch->sync step cycles).  A trace that was asked for and cannot
start raises: a run never silently drops its profile."""

from __future__ import annotations

import functools

import jax


def wrap_root(fn, name: str):
    """Name a serving root's trace (``serving_root.<name>`` scope).

    The marker attribute ``__obs_name__`` lets the auditor CLI verify the
    registry hands out instrumented builds (``--require-instrumented``)."""

    @functools.wraps(fn)
    def wrapped(*args):
        with jax.named_scope(f"serving_root.{name}"):
            return fn(*args)

    wrapped.__obs_name__ = name
    return wrapped


def annotation(name: str):
    """Host-side profiler span."""
    return jax.profiler.TraceAnnotation(name)


class ProfileCapture:
    """Capture a ``jax.profiler`` trace of N engine steps into a directory
    (viewable with TensorBoard's profile plugin / Perfetto).

    The engine calls ``tick_dispatch()`` before each root dispatch and
    ``tick_consume()`` after each consumed step; the capture starts on the
    first dispatch and stops once ``n_steps`` steps have been consumed.
    A failure to start or stop the trace (no profiler backend, a trace
    already running) propagates."""

    def __init__(self, profile_dir: str, n_steps: int = 8):
        if n_steps < 1:
            raise ValueError(f"n_steps must be >= 1, got {n_steps}")
        self.profile_dir = profile_dir
        self.n_steps = n_steps
        self.started = False
        self.finished = False
        self._consumed = 0

    def tick_dispatch(self) -> None:
        if self.started or self.finished:
            return
        jax.profiler.start_trace(self.profile_dir)
        self.started = True

    def tick_consume(self) -> None:
        if not self.started or self.finished:
            return
        self._consumed += 1
        if self._consumed >= self.n_steps:
            self.stop()

    def stop(self) -> None:
        running = self.started and not self.finished
        self.finished = True
        if running:
            jax.profiler.stop_trace()
