"""Useful ops of the window per second over chips x the int8 peak (%):
the whole step's share of the chip, which bounds every kernel's."""


def read(run):
    if not run.ops or not run.window_s > 0:
        return None
    rate = run.ops / run.window_s
    return 100.0 * rate / (run.chips * run.peak["int8_ops_per_s"])
