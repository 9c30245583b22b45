"""Encode-fused batched training kernel (``train_window_batch_encode``):
one launch presents one sample to each of ``blocks`` populations.

Convention for the STDP update: 2 more operations per synapse per cycle
(the LTP OR and the LTD AND), counted on every cycle whether or not the
neuron fired, so the count does not depend on the data; with the
forward pass that is 4 per synapse per cycle.  The LFSR draws (per
32-synapse word) are not counted.
"""

from __future__ import annotations

from chip.work import words

TRACE_NAMES = ("train_window_batch_encode",)


def work(cfg: dict, t_lens) -> tuple[float, float]:
    """``t_lens`` holds one entry per population stream in the launch."""
    n_in = cfg["n_inputs"]
    n = cfg["n_neurons"] // cfg["blocks"]
    w = words(n_in)
    ops = sum(4.0 * n_in * n * t for t in t_lens)
    state = len(t_lens) * 4 * n * w * 4       # weights + LFSR, in and out
    inputs = len(t_lens) * (n_in + 4 + 4 + 2 * n * 4)   # + seed, ltp, teach, v
    outputs = sum(t * n + n * 4 for t in t_lens)         # raster + v
    return ops, float(state + inputs + outputs)
