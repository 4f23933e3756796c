"""Generated tokens that reached the harness in the window, per second of
the window."""

from harness import loadgen


def read(run):
    return loadgen.tokens_in_window(run.window) / run.window.seconds
