"""Serving roots: device milliseconds per execution of the paged decode
root (``jit_paged_decode_step`` in the trace's XLA Modules line)."""

MODULE = "jit_paged_decode_step"


def read(run):
    calls = run.trace.module_calls(MODULE)
    return sum(calls) / len(calls) * 1e3 if calls else None
