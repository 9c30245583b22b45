"""The serving step's spans and stats, read back from a profiler trace
(``snn.*`` host events on the device trace's clock)."""

import dataclasses
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

import repro.core  # noqa: F401  (initialises repro.engine's imports)
from repro.engine import SNNEnginePlan
from repro.serving import SNNRequest, SNNServingEngine, SNNServingPolicy
from repro.serving import spans

W = 4
PLAN = SNNEnginePlan(threshold=40, leak=3, w_exp=None, max_batch=3,
                     encode="kernel")
PHASES = ("pad", "put", "dispatch", "fetch")


@dataclasses.dataclass
class Event:
    name: str
    start: int
    end: int
    stats: dict
    line: tuple

    def inside(self, other: "Event") -> bool:
        return (self.line == other.line and other.start <= self.start
                and self.end <= other.end)


def _weights(n, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, 2**32, (n, W), dtype=np.uint32))


def _request(rid, t_steps):
    rng = np.random.default_rng(500 + rid)
    return SNNRequest(rid=rid, intensities=rng.integers(
        0, 256, (W * 32,), dtype=np.uint8), n_steps=t_steps, seed=rid)


def _traced(tmp_path: Path, serve) -> list[Event]:
    """The ``snn.*`` events ``serve()`` writes under the profiler, in
    order of start."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        serve()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("snn."):
                    out.append(Event(ev.name[4:], ev.start_ns,
                                     ev.start_ns + ev.duration_ns,
                                     {k: v for k, v in ev.stats},
                                     (plane.name, line.name)))
    return sorted(out, key=lambda e: (e.start, -e.end))


def _named(events, name):
    return [e for e in events if e.name == name]


def test_served_batch_span_tree_and_stats(tmp_path):
    eng = SNNServingEngine(_weights(20), PLAN)
    eng.run([_request(0, 10)])          # compile outside the trace
    for i in range(2):
        eng.submit(_request(1 + i, 10))
    events = _traced(tmp_path, eng.step)

    (step,) = _named(events, "step")
    assert step.stats["step"] == 1 and step.stats["batch"] == 2
    assert step.stats["cpu_us"] > 0
    assert step.stats["gc"] >= 0 and step.stats["retraces"] == 0
    children = [e for e in events if e is not step]
    assert all(e.inside(step) for e in children)
    assert [e.name for e in children] == ["form", "launch", *PHASES,
                                          "guard", "finish"]
    (form,) = _named(events, "form")
    assert form.stats["queued"] == 2
    (launch,) = _named(events, "launch")
    assert launch.stats == {"kind": "serve", "level": 0, "attempt": 0,
                            "batch": 2, "slots": 3}
    phases = [_named(events, p)[0] for p in PHASES]
    assert all(p.inside(launch) for p in phases)
    assert all(a.end <= b.start for a, b in zip(phases, phases[1:]))


def test_failed_launch_attempt_is_a_span_of_its_own(tmp_path):
    def hook(ctx):
        if ctx["kind"] == "serve" and ctx["attempt"] == 0:
            raise RuntimeError("injected launch fault")

    eng = SNNServingEngine(_weights(20), PLAN, on_launch=hook)
    eng.submit(_request(0, 10))
    events = _traced(tmp_path, eng.step)
    launches = _named(events, "launch")
    assert [e.stats["attempt"] for e in launches] == [0, 1]
    assert all(e.stats["kind"] == "serve" for e in launches)
    (step,) = _named(events, "step")
    assert all(e.inside(step) for e in launches)
    (pad,) = _named(events, "pad")      # the failed attempt padded nothing
    assert pad.inside(launches[1])
    assert eng.retried == 1 and eng.windows_served == 1


def test_canary_adds_its_span_inside_finish(tmp_path):
    policy = SNNServingPolicy(canary_every=1)
    eng = SNNServingEngine(_weights(20), PLAN, policy=policy)
    eng.submit(_request(0, 10))
    events = _traced(tmp_path, eng.step)
    (canary,) = _named(events, "canary")
    (finish,) = _named(events, "finish")
    assert canary.inside(finish)
    kinds = [e.stats["kind"] for e in _named(events, "launch")
             if e.inside(canary)]
    assert kinds == ["canary"]
    assert eng.canary_checks == 1 and eng.canary_failures == 0


def test_retraces_count_a_new_window_bucket_once(tmp_path):
    # 13 neurons: a weight shape no other test serves, so every bucket
    # here starts cold
    eng = SNNServingEngine(_weights(13, seed=7), PLAN)

    def serve():
        for rid, t in enumerate((16, 12, 24, 20)):  # buckets 16, 16, 24, 24
            eng.submit(_request(rid, t))
            eng.step()

    steps = _named(_traced(tmp_path, serve), "step")
    got = [e.stats["retraces"] for e in steps]
    assert got[0] > 0 and got[2] > 0
    assert got[1] == 0 and got[3] == 0


def test_profiler_off_reads_no_costly_stat(monkeypatch):
    def forbidden(*args, **kwargs):
        raise AssertionError("read while no profiler runs")

    monkeypatch.setattr(TraceAnnotation, "set_metadata", forbidden)
    monkeypatch.setattr(time, "thread_time_ns", forbidden)
    assert not spans.enabled()
    assert spans.span("step", step=0) is spans.span("form")   # no-op
    eng = SNNServingEngine(_weights(20), PLAN)
    out = eng.run([_request(i, 10) for i in range(4)])
    assert all(r.status == "SERVED" for r in out)
    assert eng.batches == 2
