"""Idle share (%): 1 minus the union of device-operation intervals over the traced
window, averaged over the chips used."""


def read(run):
    t = run.trace
    if t is None or not t.window_s > 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
