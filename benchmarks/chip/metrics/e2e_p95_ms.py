"""95th percentile over every request offered in the window of
completion minus intended arrival (never served: infinitely late)."""

from chip.record import percentile


def read(run):
    if run.done_s is None:
        return None
    return percentile(run.latency_ms(), 95)
