"""chip.trace on a small trace recorded on one TPU v5e: 0.25 s of the
``w22a.serve-poisson`` loop at 2,000 requests/s (testdata/)."""

from __future__ import annotations

from pathlib import Path

import numpy as np
import pytest

from chip import trace

TRACE = Path(__file__).resolve().parent / "testdata" / "serve_small.xplane.pb"


@pytest.fixture(scope="module")
def raw():
    """Host spans and device events of the trace, read with plain loops."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(TRACE))
    spans, ops = [], []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                iv = (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                if plane.name.startswith("/host:") and \
                        ev.name.startswith("bench."):
                    spans.append(iv)
                if plane.name == "/device:TPU:0" and line.name == "XLA Ops":
                    ops.append(iv)
    return spans, ops


@pytest.fixture(scope="module")
def summary():
    return trace.reduce(TRACE)


def _window(spans):
    (w,) = [s for s in spans if s[0] == "bench.window"]
    return w[1], w[2]


def test_op_names():
    assert trace.op_name("%copy.8 = u32[4] copy(u32[4] %x)") == "copy"
    assert trace.op_name("%infer_window_batch_encode.1 = (s32[32,1,40]"
                         "{2,1,0}) custom-call(...)") == \
        "infer_window_batch_encode"
    assert trace.op_name("%while.2 = (s32[]) while(...)") == "while"


def test_busy_is_the_union_of_device_operations(raw, summary):
    spans, ops = raw
    w0, w1 = _window(spans)
    # independent count: a 100 ns grid over the window
    grid = np.zeros(int((w1 - w0) // 100) + 1, bool)
    for _, s, e in ops:
        s, e = max(s, w0), min(e, w1)
        if e > s:
            grid[int((s - w0) // 100):int(np.ceil((e - w0) / 100))] = True
    busy = grid.sum() * 100e-9
    assert summary.devices == 1
    assert summary.window_s == pytest.approx((w1 - w0) * 1e-9)
    assert summary.busy_s == pytest.approx(busy, rel=0.02)
    assert 0 < summary.busy_s < summary.window_s


def test_device_time_per_kernel_by_name(raw, summary):
    spans, ops = raw
    w0, w1 = _window(spans)
    kern = [(s, e) for n, s, e in ops
            if n.startswith("%infer_window_batch_encode.")
            and s >= w0 and e <= w1]
    seconds, launches = summary.kernel(("infer_window_batch_encode",))
    assert launches == len(kern) > 0
    assert seconds == pytest.approx(sum(e - s for s, e in kern) * 1e-9)
    # one launch per serving step
    steps = [s for s in spans if s[0] == "bench.step"]
    assert launches == len(steps)
    assert sum(summary.op_count.values()) >= launches


def test_idle_gaps_are_labelled_by_the_harness_spans(summary):
    idle = summary.idle_by_span
    assert set(idle) <= {"step", "submit", "wait", "other"}
    assert sum(idle.values()) == pytest.approx(
        summary.window_s - summary.busy_s, rel=1e-6)
    # the device waits mostly while the host runs the serving step
    assert max(idle, key=idle.get) == "step"
    labels = [g[0] for g in summary.longest_gaps]
    assert len(labels) == 10 and set(labels) <= set(idle)
    got = summary.breakdown()
    assert got["device_ops"][0][0] == "infer_window_batch_encode"
    assert len(got["device_ops"]) <= 10 and len(got["idle_gaps"]) <= 10
