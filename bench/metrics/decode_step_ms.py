"""Engine loop: median wall time of one consumed decode step in the window
(dispatch, device wait and host bookkeeping; the engine's own
``step_times``, as ``stats()["step_p50_s"]`` reports them)."""

from harness import measure


def read(run):
    return measure.ms(measure.pct(run.window.step_times, 50))
