"""Roofline share (%) of the encode-fused infer kernel: the larger of
useful ops over the int8 peak and bytes over HBM bandwidth, divided by
the kernel's device time."""

from chip.metrics._roofline import share


def read(run):
    return share(run, "infer_encode")
