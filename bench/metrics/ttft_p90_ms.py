"""90th percentile of the time to first token, over every request whose
first token reached the harness in the window, timed from when it was due
(open loop) or sent (closed loop)."""

from harness import loadgen, measure


def read(run):
    return measure.ms(measure.pct(loadgen.ttft_samples(run.window), 90))
