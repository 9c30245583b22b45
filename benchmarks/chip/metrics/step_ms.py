"""Mean host time of a serving step that launched: the harness's span
around each step() that served a batch, total over count."""


def read(run):
    if run.step_s is None or run.step_s.size == 0:
        return None
    return float(run.step_s.sum() / run.step_s.size * 1e3)
