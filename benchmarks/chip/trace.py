"""Reduce a JAX profiler trace (``.xplane.pb``) to what the metrics read.

* Device busy time: the union of the intervals in which an operation of
  the ``XLA Ops`` line ran on a device plane, inside the measured window
  (the harness's ``bench.window`` host span).  Control-flow containers
  (``while``, ``conditional``, ``call``) are left out: they span the
  operations they run, and the gaps between those are the device's own.
* Device time and count per operation name: ``%train_window_batch_encode.6
  = (...)`` is named ``train_window_batch_encode``; a Pallas kernel's
  operation is named after the jitted op that calls it.
* Idle gaps: the complement of busy time in the window, each labelled by
  the harness span (``bench.*``, other than the window) that overlaps it
  most, or ``other`` where none does.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from pathlib import Path

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
CONTAINERS = ("while", "conditional", "call")
_SUFFIX = re.compile(r"\.\d+$")


def op_name(event_name: str) -> str:
    """``%copy.8 = u32[...] copy(...)`` -> ``copy``."""
    head = event_name.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head)


@dataclasses.dataclass
class TraceSummary:
    window_s: float                    # length of the measured window
    busy_s: float                      # mean over the devices traced
    devices: int
    op_time_s: dict[str, float]        # summed over devices
    op_count: dict[str, int]
    idle_by_span: dict[str, float]     # idle seconds by host span (device 0)
    longest_gaps: list[tuple[str, float]]

    def kernel(self, names) -> tuple[float, int]:
        """(device seconds, launches) of the operations named ``names``."""
        return (sum(self.op_time_s.get(n, 0.0) for n in names),
                sum(self.op_count.get(n, 0) for n in names))

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_time_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def find_xplane(log_dir) -> Path:
    found = sorted(Path(log_dir).glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _label(gap, spans, starts, longest) -> str:
    """The host span that overlaps ``gap`` most (spans sorted by start,
    none longer than ``longest``)."""
    s, e = gap
    best, best_overlap = "other", 0.0
    i = bisect.bisect_right(starts, e)
    for j in range(i - 1, -1, -1):
        name, ss, se = spans[j]
        if ss + longest < s:
            break
        overlap = min(e, se) - max(s, ss)
        if overlap > best_overlap:
            best, best_overlap = name, overlap
    return best


def reduce(path) -> TraceSummary:
    """Reduce one trace file; times in the result are seconds."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    spans, window = [], None
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if not ev.name.startswith(SPAN_PREFIX):
                        continue
                    iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    if ev.name == WINDOW_SPAN:
                        window = iv
                    else:
                        spans.append((ev.name[len(SPAN_PREFIX):],) + iv)
        elif plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            ops = [(op_name(ev.name), ev.start_ns,
                    ev.start_ns + ev.duration_ns)
                   for line in plane.lines if line.name == "XLA Ops"
                   for ev in line.events]
            if ops:
                devices.append(ops)
    if not devices:
        raise ValueError(f"{path}: no device operations in the trace")
    if window is None:
        window = (min(s for d in devices for _, s, _ in d),
                  max(e for d in devices for _, _, e in d))
    w0, w1 = window
    op_time: dict[str, float] = {}
    op_count: dict[str, int] = {}
    busy_total = 0.0
    busy0 = None
    for ops in devices:
        leaf = []
        for name, s, e in ops:
            s, e = max(s, w0), min(e, w1)
            if e <= s or name in CONTAINERS:
                continue
            leaf.append((s, e))
            op_time[name] = op_time.get(name, 0.0) + (e - s) * 1e-9
            op_count[name] = op_count.get(name, 0) + 1
        busy = _union(leaf)
        busy_total += sum(e - s for s, e in busy)
        if busy0 is None:
            busy0 = busy
    spans.sort(key=lambda x: x[1])
    starts = [s for _, s, _ in spans]
    longest = max((e - s for _, s, e in spans), default=0)
    gaps, t = [], w0
    for s, e in busy0 + [[w1, w1]]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    idle: dict[str, float] = {}
    labelled = []
    for g in gaps:
        name = _label(g, spans, starts, longest)
        idle[name] = idle.get(name, 0.0) + (g[1] - g[0]) * 1e-9
        labelled.append((name, (g[1] - g[0]) * 1e-9))
    labelled.sort(key=lambda x: -x[1])
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9, busy_s=busy_total / len(devices) * 1e-9,
        devices=len(devices), op_time_s=op_time, op_count=op_count,
        idle_by_span=idle, longest_gaps=labelled[:10])
