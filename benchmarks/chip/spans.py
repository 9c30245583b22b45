"""The program's own spans in a traced window, and the per-layer numbers
they give.

The serving engine writes one ``snn.step`` span per ``step()`` with its
phases nested in it on the same thread, into the profiler's host plane
(``repro/serving/spans.py``): ``snn.form``, one ``snn.launch`` per
launch attempt holding ``snn.pad``, ``snn.put``, ``snn.dispatch`` and
``snn.fetch``, then ``snn.guard`` and ``snn.finish``, which holds the
canary's own ``snn.canary`` and its launch when one is due.
:func:`collect` keeps every ``snn.step`` that starts inside the window
as a tree of the ``snn.*`` spans it holds; host time only, since the
trace's device clock can stand a millisecond or more off its host
clock, longer than most phases last.

The step's phases are ``snn.form``, ``snn.guard`` and ``snn.finish``
directly under it and the four phases directly under its own launches:
a canary's launch, inside ``snn.finish``, is counted in that phase
alone.  "Per launching step" is per ``snn.step`` whose ``batch`` stat is
above 0.  Every number is None where the program wrote no such span.

Not yet read by the harness: wiring it in takes a defaulted field
``program`` on ``trace.TraceSummary`` filled by ``collect(pd, (w0, w1))``
in ``trace.reduce``, and a reader per metric in ``metrics/``.
"""

from __future__ import annotations

import dataclasses
import statistics

PREFIX = "snn."
STEP = "step"
PHASES = ("form", "pad", "put", "dispatch", "fetch", "guard", "finish")
STALL_NS = 10e6     # a step stalls when it outlasts the median by more


@dataclasses.dataclass
class Span:
    name: str               # without the prefix
    start_ns: float
    end_ns: float
    stats: dict
    children: list[Span] = dataclasses.field(default_factory=list)

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


@dataclasses.dataclass
class ProgramSpans:
    steps: list[Span]           # the window's ``snn.step`` trees, by start


def _trees(events) -> list[Span]:
    """The outermost spans of one thread, each with the spans nested in
    it as its children."""
    roots: list[Span] = []
    open_: list[Span] = []
    for sp in sorted(events, key=lambda s: (s.start_ns, -s.end_ns)):
        while open_ and sp.end_ns > open_[-1].end_ns:
            open_.pop()
        (open_[-1].children if open_ else roots).append(sp)
        open_.append(sp)
    return roots


def collect(pd, window) -> ProgramSpans | None:
    """The ``snn.step`` trees of a ``ProfileData`` that start within
    ``window`` (ns)."""
    w0, w1 = window
    steps: list[Span] = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            found = [Span(ev.name[len(PREFIX):], ev.start_ns,
                          ev.start_ns + ev.duration_ns,
                          {k: v for k, v in ev.stats})
                     for ev in line.events if ev.name.startswith(PREFIX)]
            steps += [s for s in _trees(found)
                      if s.name == STEP and w0 <= s.start_ns < w1]
    if not steps:
        return None
    steps.sort(key=lambda s: s.start_ns)
    return ProgramSpans(steps=steps)


def launching(prog) -> list[Span]:
    return [] if prog is None else [s for s in prog.steps
                                    if s.stats.get("batch", 0) > 0]


def launches(step) -> list[Span]:
    """The step's own ``snn.launch`` spans."""
    return [c for c in step.children if c.name == "launch"]


def phases(step) -> list[Span]:
    inner = [c for ln in launches(step) for c in ln.children]
    return [c for c in step.children + inner if c.name in PHASES]


def phase_ms(prog, name: str):
    """Time in the phase ``snn.<name>`` per launching step, ms."""
    steps = launching(prog)
    if not steps:
        return None
    total = sum(c.seconds for s in steps for c in phases(s)
                if c.name == name)
    return total / len(steps) * 1e3


def idle_unspanned(prog):
    """% of the launching steps' host time that none of their phases
    covers: what the spans leave unexplained."""
    steps = launching(prog)
    wall = sum(s.seconds for s in steps)
    if not wall:
        return None
    spanned = sum(c.seconds for s in steps for c in phases(s))
    return 100.0 * (1 - spanned / wall)


def pad_share(prog):
    """% of the slots offered by the steps' serve launches that carried
    padding: 1 minus their ``batch`` over their ``slots``."""
    served = slots = 0
    for s in [] if prog is None else prog.steps:
        for c in launches(s):
            if c.stats.get("kind") == "serve":
                served += c.stats["batch"]
                slots += c.stats["slots"]
    return 100.0 * (1 - served / slots) if slots else None


def _stalls(steps) -> list[tuple[Span, float]]:
    """(step, ns over the median) of the launching steps that outlast
    the median one by more than ``STALL_NS``."""
    wall = [s.end_ns - s.start_ns for s in steps]
    med = statistics.median(wall)
    return [(s, w - med) for s, w in zip(steps, wall) if w - med > STALL_NS]


def stall_ms(prog):
    """Time (ms) by which the stalled launching steps outlast the
    median launching step, summed."""
    steps = launching(prog)
    if not steps:
        return None
    return sum(x for _, x in _stalls(steps)) * 1e-6


def stall_cpu(prog):
    """CPU share (%) of the thread in the stalled steps: near 100 it
    worked, near 0 it was held off the CPU.  None when none stalled."""
    steps = launching(prog)
    stalled = [s for s, _ in _stalls(steps)] if steps else []
    wall_us = sum(s.end_ns - s.start_ns for s in stalled) * 1e-3
    if not wall_us:
        return None
    return 100.0 * sum(s.stats["cpu_us"] for s in stalled) / wall_us


def retraces(prog):
    """jaxpr traces (jit cache misses) in the window's steps."""
    if prog is None:
        return None
    return float(sum(s.stats.get("retraces", 0) for s in prog.steps))
