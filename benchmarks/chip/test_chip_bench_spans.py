"""chip.spans: on a trace recorded on one TPU v5e (0.25 s of the
``w22a.serve-poisson`` loop at 2,000 requests/s, testdata/) against
plain loops over its raw events, and on hand-made span trees."""

from __future__ import annotations

import types
from pathlib import Path

import pytest

from chip import spans

DATA = Path(__file__).resolve().parent / "testdata"
TRACE = DATA / "serve_spans.xplane.pb"
PHASES = ("form", "pad", "put", "dispatch", "fetch", "guard", "finish")


def _read(name: str, prog):
    """The number ``name`` (``<phase>_ms`` or a function of chip.spans)
    of the reduced spans ``prog``."""
    if name.endswith("_ms") and name[:-3] in PHASES:
        return spans.phase_ms(prog, name[:-3])
    return getattr(spans, name)(prog)


NUMBERS = [f"{p}_ms" for p in PHASES] + [
    "pad_share", "idle_unspanned", "stall_ms", "stall_cpu", "retraces"]


def _profile_data(path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(path))


def _window(pd):
    (w,) = [(ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name == "bench.window"]
    return w


@pytest.fixture(scope="module")
def raw():
    """The window and the ``snn.*`` events (name, start, end, stats) of
    the trace, read with plain loops."""
    pd = _profile_data(TRACE)
    window, events = None, []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if plane.name.startswith("/host:"):
                    if ev.name == "bench.window":
                        window = (s, e)
                    elif ev.name.startswith("snn."):
                        events.append((ev.name[4:], s, e,
                                       {k: v for k, v in ev.stats}))
    return window, events


@pytest.fixture(scope="module")
def prog():
    pd = _profile_data(TRACE)
    return spans.collect(pd, _window(pd))


def _steps(raw):
    """Each ``snn.step`` starting in the window with the events inside
    it, by plain containment."""
    (w0, w1), events = raw
    out = []
    for name, s, e, stats in events:
        if name == "step" and w0 <= s < w1:
            kids = [x for x in events if x[0] != "step"
                    and s <= x[1] and x[2] <= e]
            out.append(((s, e, stats), kids))
    return out


def _descendants(span):
    for c in span.children:
        yield c
        yield from _descendants(c)


def test_steps_and_their_children(raw, prog):
    want = _steps(raw)
    assert len(want) > 20 and len(prog.steps) == len(want)
    for got, ((s, e, stats), kids) in zip(prog.steps, want):
        assert (got.start_ns, got.end_ns, got.stats) == (s, e, stats)
        assert sorted((c.name, c.start_ns) for c in _descendants(got)) == \
            sorted((k[0], k[1]) for k in kids)
        # the copies and the launch's wait sit in its own launch span
        for c in got.children:
            assert c.name in {"form", "launch", "guard", "finish"}
            if c.name == "launch":
                assert [k.name for k in c.children] == [
                    "pad", "put", "dispatch", "fetch"]
    launching = [x for x in want if x[0][2]["batch"] > 0]
    assert launching
    # every launching step on the chip holds the full span tree
    for _, kids in launching:
        assert {k[0] for k in kids} >= {"launch", *PHASES}


@pytest.mark.parametrize("phase", PHASES)
def test_phase_per_launching_step(raw, prog, phase):
    launching = [x for x in _steps(raw) if x[0][2]["batch"] > 0]
    total = sum(k[2] - k[1] for _, kids in launching for k in kids
                if k[0] == phase)
    got = _read(f"{phase}_ms", prog)
    assert got == pytest.approx(total / len(launching) * 1e-6)
    assert got > 0


def test_pad_share_and_retraces(raw, prog):
    steps = _steps(raw)
    launches = [k[3] for _, kids in steps for k in kids
                if k[0] == "launch" and k[3]["kind"] == "serve"]
    served = sum(x["batch"] for x in launches)
    slots = sum(x["slots"] for x in launches)
    assert _read("pad_share", prog) == pytest.approx(
        100 * (1 - served / slots))
    assert _read("retraces", prog) == sum(
        x[0][2]["retraces"] for x in steps) == 0


def test_idle_unspanned_against_a_plain_sum(raw, prog):
    """The share of the launching steps' time outside the seven phases,
    from the raw events by containment (no canary ran in the trace)."""
    launching = [x for x in _steps(raw) if x[0][2]["batch"] > 0]
    wall = sum(e - s for (s, e, _), _ in launching)
    spanned = sum(k[2] - k[1] for _, kids in launching for k in kids
                  if k[0] in PHASES)
    got = _read("idle_unspanned", prog)
    assert got == pytest.approx(100 * (1 - spanned / wall))
    assert 0 < got < 10


def test_a_program_without_spans_reads_nothing():
    """A trace of the program before it had spans reduces to None, and
    every number of None is None."""
    pd = _profile_data(DATA / "serve_small.xplane.pb")
    assert spans.collect(pd, _window(pd)) is None
    for name in NUMBERS:
        assert _read(name, None) is None


# --- hand-made span trees ------------------------------------------------

def _event(name, start_ms, end_ms, **stats):
    return types.SimpleNamespace(
        name="snn." + name, start_ns=round(start_ms * 1e6),
        duration_ns=round((end_ms - start_ms) * 1e6),
        stats=list(stats.items()))


def _profile(*events):
    line = types.SimpleNamespace(name="main", events=list(events))
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name="/host:CPU", lines=[line]),
        types.SimpleNamespace(name="/device:TPU:0", lines=[])])


def _step_with_canary(t0, batch=20):
    """A launching step at ``t0`` ms whose ``finish`` runs a canary."""
    return [
        _event("step", t0 + 0.0, t0 + 3.0, step=0, batch=batch),
        _event("form", t0 + 0.1, t0 + 0.2, queued=batch),
        _event("launch", t0 + 0.3, t0 + 1.9, kind="serve", level=0,
               attempt=0, batch=batch, slots=32),
        _event("pad", t0 + 0.3, t0 + 0.4),
        _event("put", t0 + 0.4, t0 + 0.6),
        _event("dispatch", t0 + 0.6, t0 + 0.7),
        _event("fetch", t0 + 0.7, t0 + 1.8),
        _event("guard", t0 + 1.9, t0 + 2.0),
        _event("finish", t0 + 2.0, t0 + 2.9),
        _event("canary", t0 + 2.1, t0 + 2.8),
        _event("launch", t0 + 2.1, t0 + 2.7, kind="canary", level=0,
               attempt=0, batch=4, slots=32),
        _event("pad", t0 + 2.1, t0 + 2.2),
        _event("put", t0 + 2.2, t0 + 2.3),
        _event("dispatch", t0 + 2.3, t0 + 2.4),
        _event("fetch", t0 + 2.4, t0 + 2.7),
    ]


def test_the_span_tree_of_a_step():
    pd = _profile(*_step_with_canary(0.0),
                  _event("step", -5.0, -1.0, step=-1, batch=32))  # before
    prog = spans.collect(pd, (0.0, 100e6))
    (step,) = prog.steps
    assert [c.name for c in step.children] == [
        "form", "launch", "guard", "finish"]
    (finish,) = [c for c in step.children if c.name == "finish"]
    (canary,) = finish.children
    assert canary.name == "canary"
    assert [c.name for c in canary.children] == ["launch"]
    assert [c.name for c in canary.children[0].children] == [
        "pad", "put", "dispatch", "fetch"]


def test_a_canary_counts_in_finish_alone():
    """The canary's launch and its phases sit inside ``snn.finish``: the
    phases of the step are counted once, and sum with the time no phase
    covers to the step."""
    prog = spans.collect(_profile(*_step_with_canary(0.0),
                                  *_step_with_canary(10.0, batch=32)),
                         (0.0, 100e6))
    want = {"form": 0.1, "pad": 0.1, "put": 0.2, "dispatch": 0.1,
            "fetch": 1.1, "guard": 0.1, "finish": 0.9}
    for name, ms in want.items():
        assert _read(f"{name}_ms", prog) == pytest.approx(ms), name
    unspanned = 3.0 - sum(want.values())
    assert _read("idle_unspanned", prog) == pytest.approx(
        100 * unspanned / 3.0)
    # serve launches only: 20 and 32 of 32 slots
    assert _read("pad_share", prog) == pytest.approx(100 * 12 / 64)


def test_spans_outside_steps_and_threads_apart():
    """Only ``snn.step`` trees count: spans outside any step are dropped,
    and a step on another thread holds none of this thread's spans."""
    main = [_event("launch", 0.0, 0.5, kind="serve", level=0, attempt=0,
                   batch=1, slots=32),              # outside any step
            _event("step", 1.0, 2.0, step=0, batch=0),
            _event("form", 1.1, 1.2, queued=0)]
    other = [_event("step", 1.0, 3.0, step=0, batch=0)]
    pd = _profile(*main)
    pd.planes[0].lines.append(types.SimpleNamespace(name="other",
                                                    events=other))
    prog = spans.collect(pd, (0.0, 100e6))
    assert [len(s.children) for s in prog.steps] == [1, 0]
    assert prog.steps[0].children[0].name == "form"
    assert _read("pad_share", prog) is None   # no serve launch
    assert _read("form_ms", prog) is None     # no launching step


def test_a_retried_launch_counts_both_attempts():
    """A failed attempt (no phases) and its retry are both the step's own
    launches: the phases of the retry count, and both attempts' slots."""
    pd = _profile(
        _event("step", 0.0, 2.0, step=0, batch=16),
        _event("launch", 0.1, 0.2, kind="serve", level=0, attempt=0,
               batch=16, slots=32),
        _event("launch", 0.2, 1.8, kind="serve", level=0, attempt=1,
               batch=16, slots=32),
        _event("pad", 0.2, 0.3),
        _event("fetch", 0.4, 1.7),
        _event("finish", 1.8, 1.9),
    )
    prog = spans.collect(pd, (0.0, 100e6))
    assert _read("pad_ms", prog) == pytest.approx(0.1)
    assert _read("fetch_ms", prog) == pytest.approx(1.3)
    assert _read("pad_share", prog) == pytest.approx(50.0)
    assert _read("idle_unspanned", prog) == pytest.approx(100 * 0.5 / 2.0)


def test_stalls_against_the_median_launching_step():
    def step(start, wall, cpu_us, batch=32):
        return _event("step", start, start + wall, step=0, batch=batch,
                      cpu_us=cpu_us, gc=0, retraces=0)

    pd = _profile(
        step(0, 3.0, 2900), step(5, 3.0, 2900), step(10, 3.0, 2900),
        step(15, 0.1, 50, batch=0),     # no batch: not a launching step
        step(20, 12.9, 12000),          # 9.9 ms over the median: no stall
        step(40, 123.0, 12300),         # 120 ms over, 10% on the CPU
    )
    prog = spans.collect(pd, (0.0, 200e6))
    assert _read("stall_ms", prog) == pytest.approx(120.0)
    assert _read("stall_cpu", prog) == pytest.approx(10.0)
    calm = spans.collect(_profile(step(0, 3.0, 2900),
                                  step(5, 12.9, 2900)), (0, 50e6))
    assert _read("stall_ms", calm) == 0.0
    assert _read("stall_cpu", calm) is None
