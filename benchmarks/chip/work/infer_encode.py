"""Encode-fused serving kernel (``infer_window_batch_encode``): each
request is uint8 intensities, a counter seed and its length."""

from __future__ import annotations

from chip.work import words

TRACE_NAMES = ("infer_window_batch_encode",)


def work(cfg: dict, t_lens) -> tuple[float, float]:
    n_in, n = cfg["n_inputs"], cfg["n_neurons"]
    ops = sum(2.0 * n_in * n * t for t in t_lens)
    weights = n * words(n_in) * 4
    inputs = len(t_lens) * (n_in + 4 + 4)     # intensities, seed, length
    outputs = len(t_lens) * n * 4             # int32 counts
    return ops, float(weights + inputs + outputs)
