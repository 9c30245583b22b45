"""Spans and counters of the serving step, on the profiler's clock.

Every span is a :class:`jax.profiler.TraceAnnotation` named ``snn.<name>``:
while a profiler runs it lands in the trace's host plane beside the
device's operations; while none runs :func:`span` gives a shared no-op
instead.  Stats that cost anything to read (thread time, collector
passes) are read only while :func:`enabled` and ride on the span as
event stats.
"""

from __future__ import annotations

import contextlib
import gc
import time

import jax
from jax.profiler import TraceAnnotation

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_retraces = 0   # jaxpr traces in this process: a jit cache miss each
_OFF = contextlib.nullcontext()


def _on_duration(event: str, duration_secs: float, **_) -> None:
    global _retraces
    if event == _TRACE_EVENT:
        _retraces += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def span(name: str, **stats):
    """The span ``snn.<name>`` with ``stats``; while no profiler runs,
    one shared no-op context, which costs less than an idle
    ``TraceAnnotation``."""
    if TraceAnnotation.is_enabled():
        return TraceAnnotation("snn." + name, **stats)
    return _OFF


def enabled() -> bool:
    """True while a profiler is recording."""
    return TraceAnnotation.is_enabled()


def counters() -> tuple[int, int, int]:
    """(thread CPU ns, collector passes, retraces) so far."""
    passes = sum(g["collections"] for g in gc.get_stats())
    return time.thread_time_ns(), passes, _retraces


def since(start: tuple[int, int, int]) -> dict:
    """The step stats ``cpu_us``, ``gc`` and ``retraces`` since ``start``
    (a :func:`counters` reading)."""
    cpu, passes, traces = counters()
    return {"cpu_us": (cpu - start[0]) / 1e3, "gc": passes - start[1],
            "retraces": traces - start[2]}
