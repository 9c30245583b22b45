"""Device time per launch of the serving kernel that ran, from the
trace."""

import importlib


def read(run):
    if run.trace is None or run.kernel is None:
        return None
    names = importlib.import_module(f"chip.work.{run.kernel}").TRACE_NAMES
    seconds, launches = run.trace.kernel(names)
    return seconds / launches * 1e6 if launches else None
