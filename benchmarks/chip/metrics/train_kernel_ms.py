"""Device time of the train kernel per presented sample (ms), from the
trace: one launch presents one sample to every stream."""

import importlib


def read(run):
    if run.trace is None or run.kernel is None:
        return None
    names = importlib.import_module(f"chip.work.{run.kernel}").TRACE_NAMES
    seconds, launches = run.trace.kernel(names)
    return seconds / launches * 1e3 if launches else None
