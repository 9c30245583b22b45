"""95th percentile of submit() time minus intended arrival: how late the
open-loop load generator ran."""

import numpy as np

from chip.record import percentile


def read(run):
    if run.submitted_s is None or run.mix.get("arrivals") != "poisson":
        return None
    lag = (run.submitted_s - run.intended_s) * 1e3
    return percentile(lag[~np.isnan(lag)], 95)
