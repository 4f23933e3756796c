"""Serving roots: device milliseconds per execution of the chunked-prefill
root (``jit_paged_prefill_chunk_step`` in the trace's XLA Modules line)."""

MODULE = "jit_paged_prefill_chunk_step"


def read(run):
    calls = run.trace.module_calls(MODULE)
    return sum(calls) / len(calls) * 1e3 if calls else None
