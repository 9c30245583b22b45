"""Shared by the ``*_roofline`` readers: the kernel's share of its
roofline over the traced window, per launch (robust to a trace that
holds fewer launches than the window made)."""

import importlib

from chip.work import roofline


def share(run, kernel: str):
    if run.trace is None or run.kernel != kernel or not run.launches:
        return None
    names = importlib.import_module(f"chip.work.{kernel}").TRACE_NAMES
    seconds, launches = run.trace.kernel(names)
    if not launches:
        return None
    got = roofline(run.ops / run.launches, run.nbytes / run.launches,
                   seconds / launches, run.peak)
    if got is None:
        return None
    run.bounds[kernel] = got[1]
    return got[0]
