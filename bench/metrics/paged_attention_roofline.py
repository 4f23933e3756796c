"""Kernel: least time of the traced paged-attention calls (the larger of
FLOPs over peak and bytes over HBM bandwidth, from the live rows and
cached tokens of each dispatch) over their device time, in percent.
Memory-bound at these shapes."""

from harness import measure


def read(run):
    least = measure.paged_attention_least_s(run)
    spent = measure.kernel_time_s(run, "paged_attention")
    return 100.0 * least / spent if least and spent else None
