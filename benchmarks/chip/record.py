"""What one run measured, for the metric readers."""

from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass
class Run:
    """Fields a loop fills; a reader that finds its field empty
    returns None and its metric is left out of the result line."""
    cfg: dict
    mix: dict
    chips: int
    peak: dict
    setup_s: float = math.nan
    window_s: float = math.nan      # seconds the measured window lasted
    completed: int = 0              # windows or samples done in the window
    attempted: int = 0
    failed: int = 0
    # --- serve: one entry per request offered in the window -------------
    intended_s: np.ndarray | None = None   # intended arrival, from t0
    submitted_s: np.ndarray | None = None  # submit() called, from t0
    done_s: np.ndarray | None = None       # answer back (nan: never)
    queue_wait_ms: np.ndarray | None = None  # the engine's own stamp
    step_s: np.ndarray | None = None       # each launching step()'s time
    # --- kernel work of every launch in the window ------------------------
    kernel: str | None = None       # module name under chip/work/
    launches: int = 0
    ops: float = 0.0
    nbytes: float = 0.0
    trace: object = None            # chip.trace.TraceSummary (--trace 1)
    memory_peak: int | None = None  # bytes, the fullest chip
    bounds: dict = dataclasses.field(default_factory=dict)  # kernel: roof
    checks: dict = dataclasses.field(default_factory=dict)  # name: (value, limit)
    extra: dict = dataclasses.field(default_factory=dict)   # for the control

    def latency_ms(self) -> np.ndarray:
        """Completion minus intended arrival for every request offered;
        a request that never came back is infinitely late."""
        lat = (self.done_s - self.intended_s) * 1e3
        return np.where(np.isnan(lat), np.inf, lat)


def percentile(values, q: float) -> float | None:
    """Nearest-rank percentile (exact: every sample is kept)."""
    v = np.sort(np.asarray(values, dtype=np.float64))
    if v.size == 0:
        return None
    return float(v[max(0, math.ceil(q / 100.0 * v.size) - 1)])
