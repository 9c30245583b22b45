"""Median over every request offered in the window of completion minus
intended arrival (a request never served is infinitely late)."""

from chip.record import percentile


def read(run):
    if run.done_s is None:
        return None
    return percentile(run.latency_ms(), 50)
