"""Load generator: 95th percentile of how late the harness called submit()
after a request was due, over the window's open-loop arrivals.  A starved
generator reads high here, not as a slow server."""

from harness import measure


def read(run):
    if run.cell.traffic["loop"] != "open":
        return None
    late = [s.sent - s.due for s in run.window.sent
            if measure.in_window(run, s.sent)]
    return measure.ms(measure.pct(late, 95))
