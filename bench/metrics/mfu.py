"""Whole step: useful model FLOPs of the tokens the traced window
processed over the window's length times the peak of every chip traced,
in percent.
Reads ``mfu.<suffix>`` for every suffix; BENCHMARK.json's ``workloads``
says which cells report which."""

from harness import measure


def read(run):
    flops = measure.useful_flops(run)
    return 100.0 * flops / (run.trace.window_s * run.peaks["flops_bf16"]
                            * run.trace.devices)
