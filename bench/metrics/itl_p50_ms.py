"""Median gap between consecutive tokens of one request, over every gap
that ends in the window, all requests pooled."""

from harness import loadgen, measure


def read(run):
    return measure.ms(measure.pct(loadgen.itl_samples(run.window), 50))
