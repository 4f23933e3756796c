"""95th percentile of the gaps between consecutive tokens of one request
that end in the window."""

from harness import loadgen, measure


def read(run):
    return measure.ms(measure.pct(loadgen.itl_samples(run.window), 95))
