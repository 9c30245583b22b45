"""Excitatory spikes per sample presented in the window: the program's
``spikes`` counter (the trainer's ``snn.train`` stat) over the samples.
Fixed by the model's dynamics while ``correct`` holds."""


def read(run):
    spikes = run.extra.get("spikes")
    if spikes is None or not run.completed:
        return None
    return spikes / run.completed
