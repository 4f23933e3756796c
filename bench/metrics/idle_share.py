"""Device: share of the traced window in which no operation ran on the
chip, in percent (1 minus the union of the op intervals over the window).
Reads ``idle_share.<suffix>`` for every suffix; BENCHMARK.json's
``workloads`` says which cells report which."""


def read(run):
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
