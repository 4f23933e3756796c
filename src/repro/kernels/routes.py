"""Which path each kernel call site took, tallied at trace time.

A caller that wants the tally opens ``count_routes()``; the ops wrappers
note ``(kernel, "pallas" | "xla")`` for every call site they trace while
it is open.  A jitted root adds its call sites once per trace.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars

_TALLY = contextvars.ContextVar("kernel_routes", default=None)


@contextlib.contextmanager
def count_routes():
    tally = collections.Counter()
    token = _TALLY.set(tally)
    try:
        yield tally
    finally:
        _TALLY.reset(token)


def note(kernel: str, path: str) -> None:
    tally = _TALLY.get()
    if tally is not None:
        tally[(kernel, path)] += 1
