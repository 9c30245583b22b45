"""95th percentile over served requests of the engine's queue wait
(batch formation minus intended arrival), as the engine stamps it."""

import numpy as np

from chip.record import percentile


def read(run):
    if run.queue_wait_ms is None:
        return None
    q = run.queue_wait_ms[~np.isnan(run.queue_wait_ms)]
    return percentile(q, 95) if q.size else None
