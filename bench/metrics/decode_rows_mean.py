"""Scheduler: mean live rows per decode dispatch in the window (the live
row count the engine reports with each dispatch; the engine's
``scheduler_stats()["mean_live_rows"]`` restricted to the window)."""

from harness import measure


def read(run):
    rows = [r for _, r, _ in measure.dispatches(run)]
    return float(sum(rows) / len(rows)) if rows else None
