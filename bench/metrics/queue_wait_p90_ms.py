"""Scheduler: 90th percentile of the engine's own submit-to-admission
wait (its ``on_admit`` hook), over admissions in the window."""

from harness import measure


def read(run):
    waits = [w for t, _, w in run.window.obs.admits
             if measure.in_window(run, t)]
    return measure.ms(measure.pct(waits, 90))
