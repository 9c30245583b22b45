"""Roofline share (%) of the encode-fused train kernel (see
chip/work/train_encode.py for the STDP convention)."""

from chip.metrics._roofline import share


def read(run):
    return share(run, "train_encode")
