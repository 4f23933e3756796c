"""Kernel: least time of the traced nested low-rank kernel calls over the
device time they took, in percent.  The factors XLA moves from HBM into
VMEM for a call count with the time of the op that moved them
(``measure.nested_lowrank_times``), so the share reads the same whether
the kernel streams its factors itself or XLA stages them."""

from harness import measure


def read(run):
    t = measure.nested_lowrank_times(run)
    return 100.0 * t[0] / t[1] if t and t[0] and t[1] else None
