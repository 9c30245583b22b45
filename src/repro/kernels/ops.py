"""jit'd public wrappers around the Pallas kernels.

Handles TPU-alignment padding (word axis -> multiple of 128 lanes,
neuron axis -> multiple of the block size) and backend dispatch:

  backend="ref"     pure-jnp oracle (XLA; used inside scans and dry-runs)
  backend="interp"  Pallas interpret mode (CPU container: kernel body
                    executed in Python — correctness validation)
  backend="tpu"     compiled pl.pallas_call (the deployment target)

The engine (repro.engine) calls the *window* ops: ``train_window_batch``
and ``train_window_batch_encode`` to train, ``infer_window_batch`` and
``infer_window_batch_encode`` to serve.  One launch covers the whole
T-cycle presentation window of B streams with weights/LFSR resident in
VMEM, instead of T per-cycle launches that round-trip state through
HBM.  Their ref path is a scan of per-cycle steps in XLA
(``kernels/ref.py``); on TPU the ``backend="tpu"`` window kernels are
the deployment target.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels import snn_kernels as _k

_LANES = 128
_SAMPLE_BLOCK = 32   # the MXU serving kernel's sample block: one int8 tile
_NEVER = 1 << 30     # the threshold offset of a padded neuron


def _pad_to(x: jnp.ndarray, axis: int, mult: int, fill=0) -> jnp.ndarray:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


def _block_n(n_padded: int) -> int:
    return min(128, n_padded)


def _pad_state(x: jnp.ndarray, bn: int, fill=0) -> jnp.ndarray:
    """Pad an [n, w] state matrix to lane/block alignment.

    LFSR states must use fill=1: padded lanes have to be nonzero (0 is
    the PRNG's absorbing state); the value itself is never read back.
    """
    return _pad_to(_pad_to(x, 1, _LANES, fill=fill), 0, bn, fill=fill)


def _pad_window(spike_train: jnp.ndarray, t_chunk: int | None
                ) -> tuple[jnp.ndarray, int]:
    """Zero-pad the time axis (axis -2) to a t_chunk multiple.

    Returns (padded train, effective chunk).  Padded cycles are masked
    inside the kernels via the ``t_total`` literal, so chunked and
    unchunked launches are bit-exact.
    """
    t_steps = spike_train.shape[-2]
    tc = t_steps if t_chunk is None else max(1, min(t_chunk, t_steps))
    return _pad_to(spike_train, spike_train.ndim - 2, tc), tc


def _prep(weights, pre, block_w_mult=_LANES):
    n, w = weights.shape
    bn = _block_n(max(8, n))
    wp = _pad_to(_pad_to(weights, 1, block_w_mult), 0, max(bn, 8))
    pp = _pad_to(pre, 0, block_w_mult)
    return wp, pp, bn


@functools.partial(jax.jit, static_argnames=("backend",))
def spike_process(spikes, weights, *, backend: str = "ref"):
    """SPU: counts i32[n] = popcount(spikes & weights[i]) per row."""
    if backend == "ref":
        return _ref.spike_process_ref(spikes, weights)
    n, _ = weights.shape
    wp, pp, bn = _prep(weights, spikes)
    out = _k.spike_process(pp, wp, block_n=max(bn, 8),
                           block_w=min(wp.shape[1], 512),
                           interpret=(backend == "interp"))
    return out[:n]


@functools.partial(jax.jit, static_argnames=("threshold", "leak", "backend"))
def lif_step(v, count, threshold: int, leak: int, *, backend: str = "ref"):
    if backend == "ref":
        return _ref.lif_step_ref(v, count, threshold, leak)
    n = v.shape[0]
    bn = _block_n(max(8, n))
    vp = _pad_to(v, 0, bn)
    cp = _pad_to(count, 0, bn)
    v2, f = _k.lif_step(vp, cp, threshold, leak, block_n=bn,
                        interpret=(backend == "interp"))
    return v2[:n], f[:n]


@functools.partial(jax.jit, static_argnames=(
    "w_exp", "gain", "n_syn", "ltp_prob", "backend"))
def stdp_update(weights, pre_spikes, post_fired, lfsr_state, *,
                w_exp: int, gain: int, n_syn: int, ltp_prob: int = 1023,
                backend: str = "ref"):
    if backend == "ref":
        return _ref.stdp_update_ref(weights, pre_spikes, post_fired,
                                    lfsr_state, w_exp, gain, n_syn, ltp_prob)
    n, w = weights.shape
    wp, pp, bn = _prep(weights, pre_spikes)
    fp = _pad_to(post_fired, 0, max(bn, 8))
    sp = _pad_state(lfsr_state, max(bn, 8), fill=1)
    w2, s2 = _k.stdp_update(wp, pp, fp, sp, w_exp=w_exp, gain=gain,
                            n_syn=n_syn, ltp_prob=ltp_prob,
                            block_n=max(bn, 8),
                            interpret=(backend == "interp"))
    return w2[:n, :w], s2[:n, :w]


@functools.partial(jax.jit, static_argnames=(
    "threshold", "leak", "w_exp", "gain", "n_syn", "ltp_prob", "train",
    "backend"))
def fused_snn_step(weights, pre_spikes, v, lfsr_state, teach, *,
                   threshold: int, leak: int, w_exp: int, gain: int,
                   n_syn: int, ltp_prob: int = 1023, train: bool = True,
                   backend: str = "ref"):
    """The paper's coarse-granularity ``snn.step`` as one fused kernel."""
    if backend == "ref":
        return _ref.fused_snn_step_ref(
            weights, pre_spikes, v, lfsr_state, teach, threshold, leak,
            w_exp, gain, n_syn, ltp_prob, train)
    n, w = weights.shape
    wp, pp, bn = _prep(weights, pre_spikes)
    bn = max(bn, 8)
    vp = _pad_to(v, 0, bn)
    tp = _pad_to(teach, 0, bn)
    sp = _pad_state(lfsr_state, bn, fill=1)
    w2, v2, f, s2 = _k.fused_snn_step(
        wp, pp, vp, sp, tp, threshold=threshold, leak=leak, w_exp=w_exp,
        gain=gain, n_syn=n_syn, ltp_prob=ltp_prob, train=train,
        block_n=bn, interpret=(backend == "interp"))
    return w2[:n, :w], v2[:n], f[:n], s2[:n, :w]


@functools.partial(jax.jit, static_argnames=(
    "threshold", "leak", "w_exp", "gain", "n_syn", "t_chunk", "backend"))
def train_window_batch(weights, spike_trains, v, lfsr_state, teach, *,
                       threshold: int, leak: int, w_exp: int, gain: int,
                       n_syn: int, ltp_prob=1023,
                       t_chunk: int | None = None, backend: str = "ref"):
    """Batched training grid: B independent streams per launch.

    weights/lfsr u32[B, n, w], spike_trains u32[B, T, w], v i32[B, n],
    teach i32[B, n] — per-stream regfiles, one grid ordered
    (neuron-block major, batch, time-chunk minor).  ``ltp_prob`` is a
    shared int or a per-stream i32[B] vector (an SMEM scalar operand of
    the kernel, so each stream can keep its own active-learning
    schedule).  Bit-exact with B sequential one-stream windows
    (:func:`repro.kernels.ref.fused_snn_window_ref`), including each
    stream's LFSR sequence.  B=1 is one stream's window.
    Returns (weights', v', fired bool[B, T, n], lfsr').
    """
    if backend == "ref":
        return _ref.train_window_batch_ref(
            weights, spike_trains, v, lfsr_state, teach, threshold, leak,
            w_exp, gain, n_syn, ltp_prob)
    b, n, w = weights.shape
    t_steps = spike_trains.shape[1]
    bn = max(_block_n(max(8, n)), 8)
    wp = _pad_to(_pad_to(weights, 2, _LANES), 1, bn)
    stp, tc = _pad_window(_pad_to(spike_trains, 2, _LANES), t_chunk)
    vp = _pad_to(v, 1, bn)
    tp = _pad_to(teach, 1, bn)
    sp = _pad_to(_pad_to(lfsr_state, 2, _LANES, fill=1), 1, bn, fill=1)
    w2, v2, f, s2 = _k.train_window_batch(
        wp, stp, vp, sp, tp, threshold=threshold, leak=leak, w_exp=w_exp,
        gain=gain, n_syn=n_syn, ltp_prob=ltp_prob, block_n=bn,
        t_chunk=tc, t_total=t_steps, interpret=(backend == "interp"))
    return (w2[:, :n, :w], v2[:, :n], f[:, :t_steps, :n], s2[:, :n, :w])


def _intensity_words(intensities: jnp.ndarray, words: int) -> jnp.ndarray:
    """uint8[..., n_in] -> uint32[..., 8, words] intensity words.

    The encode kernels' operand layout: byte ``b`` of word ``[k, wi]``
    is the intensity of input ``wi*32 + 4k + b`` (4 intensities per
    uint32 lane — the whole operand is n_in bytes, the T/8x input-stream
    saving the encode path exists for).  ``words`` is the (already
    lane-padded) spike-word width; padding intensities are zero, so
    padded inputs never fire.
    """
    x = jnp.asarray(intensities, jnp.uint32)
    pad = words * 32 - x.shape[-1]
    if pad < 0:
        raise ValueError(f"{x.shape[-1]} intensities exceed the "
                         f"{words}-word spike width ({words * 32} inputs)")
    if pad:
        widths = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
        x = jnp.pad(x, widths)
    x = x.reshape(x.shape[:-1] + (words, 8, 4))
    w = (x[..., 0]
         | jnp.left_shift(x[..., 1], jnp.uint32(8))
         | jnp.left_shift(x[..., 2], jnp.uint32(16))
         | jnp.left_shift(x[..., 3], jnp.uint32(24)))
    return jnp.swapaxes(w, -1, -2)


@functools.partial(jax.jit, static_argnames=(
    "n_steps", "threshold", "leak", "w_exp", "gain", "n_syn", "t_chunk",
    "backend", "w_inh", "theta_plus"))
def train_window_batch_encode(weights, intensities, seeds, v, lfsr_state,
                              teach, *, n_steps: int, threshold: int,
                              leak: int, w_exp: int, gain: int,
                              n_syn: int, ltp_prob=1023,
                              t_chunk: int | None = None,
                              backend: str = "ref", theta=None,
                              w_inh: int = 0, theta_plus: int = 0):
    """:func:`train_window_batch` with in-kernel encode.

    intensities uint8[B, n_in], seeds int | i32[B] (per-stream counter
    bases, an SMEM scalar operand like ``ltp_prob``).  Bit-exact with
    host-encoding each stream and running the pre-packed batch op.

    ``theta`` i32[B, n] adds an adaptive threshold per neuron (fire at
    ``threshold + θ``; ``θ += theta_plus`` on each spike) and, with
    ``w_inh``, lateral inhibition within each stream's population
    (:func:`repro.kernels.ref.train_window_wta_ref`); θ' and
    ``recomputes`` i32[B] are then a fifth and a sixth result.
    ``recomputes`` counts the (cycle, 128-neuron group) pairs with a
    spike: each is one STDP update of the group's rows, after which the
    kernel recomputes the group's input currents from the new weights.
    ``w_inh`` and ``theta_plus`` need ``theta``.  With θ the population
    is one neuron block (padded to 128s; a padded neuron's threshold is
    out of reach), its currents computed on the MXU a block of cycles
    at a time (:func:`repro.kernels.snn_kernels._train_window_enc_coupled`);
    the kernel also takes the intensities one per lane, for its dense
    spike tile.
    Returns (weights', v', fired bool[B, T, n], lfsr'[, theta',
    recomputes]).
    """
    if theta is None and (w_inh or theta_plus):
        raise ValueError("w_inh and theta_plus need the theta operand")
    if backend == "ref":
        return _ref.train_window_batch_encode_ref(
            weights, intensities, seeds, v, lfsr_state, teach, n_steps,
            threshold, leak, w_exp, gain, n_syn, ltp_prob, theta=theta,
            w_inh=w_inh, theta_plus=theta_plus)
    b, n, w = weights.shape
    bn = max(_block_n(max(8, n)), 8)
    if theta is not None:
        bn = -(-n // _LANES) * _LANES   # one block: the whole population
    wp = _pad_to(_pad_to(weights, 2, _LANES), 1, bn)
    iw = _intensity_words(intensities, wp.shape[2])
    vp = _pad_to(v, 1, bn)
    tp = _pad_to(teach, 1, bn)
    sp = _pad_to(_pad_to(lfsr_state, 2, _LANES, fill=1), 1, bn, fill=1)
    # padded neurons never fire: their threshold is out of reach
    thp = None if theta is None else _pad_to(theta, 1, bn, fill=_NEVER)
    x = (None if theta is None else
         _pad_to(jnp.asarray(intensities, jnp.int32), 1, _LANES))
    out = _k.train_window_batch_encode(
        wp, iw, seeds, vp, sp, tp, n_steps=n_steps, threshold=threshold,
        leak=leak, w_exp=w_exp, gain=gain, n_syn=n_syn,
        ltp_prob=ltp_prob, block_n=bn, t_chunk=t_chunk, theta=thp,
        intensities=x, w_inh=w_inh, theta_plus=theta_plus,
        interpret=(backend == "interp"))
    w2, v2, f, s2 = out[:4]
    res = (w2[:, :n, :w], v2[:, :n], f[:, :n_steps, :n], s2[:, :n, :w])
    return res if theta is None else res + (out[4][:, :n], out[5])


@functools.partial(jax.jit, static_argnames=("n_steps", "threshold",
                                             "leak", "t_chunk", "backend"))
def infer_window_batch_encode(weights, intensities, seeds, *,
                              n_steps: int, threshold: int, leak: int,
                              t_total=None, t_chunk: int | None = None,
                              backend: str = "ref"):
    """Intensity-resident serving: :func:`infer_window_batch` with
    in-kernel encode and per-sample window lengths.

    intensities uint8[B, n_in], seeds int | i32[B].  ``t_total``
    (i32[B], optional) is each sample's true window length — a traced
    operand, NOT a static — so ragged serving batches share one
    compiled launch per (B, n_steps) bucket.  Returns counts i32[B, n];
    bit-exact in counts with host-encode + zero-mask + pre-packed serve
    (requires threshold >= 1, which serving enforces).
    """
    if backend == "ref":
        return _ref.infer_window_batch_encode_ref(
            weights, intensities, seeds, n_steps, threshold, leak,
            t_total)
    n, w = weights.shape
    b, n_in = intensities.shape
    if n_in > w * 32:
        raise ValueError(f"{n_in} intensities exceed the {w}-word spike "
                         f"width ({w * 32} inputs)")
    # the MXU kernel's layout: a neuron block of 256 (128 where n does
    # not split into 256s), a sample block of 32 (one int8 tile of rows)
    # and an input per lane.  Padded inputs have intensity 0 and padded
    # samples window length 0, so neither fires.
    bn = 256 if -(-n // _LANES) % 2 == 0 else _LANES
    wp = _pad_to(_pad_to(weights, 1, _LANES), 0, bn)
    x = _pad_to(_pad_to(jnp.asarray(intensities, jnp.int32), 1, _LANES),
                0, _SAMPLE_BLOCK)
    sd = jnp.broadcast_to(jnp.asarray(seeds, jnp.int32), (b,))
    tt = (jnp.full((b,), n_steps, jnp.int32) if t_total is None
          else jnp.asarray(t_total, jnp.int32))
    counts = _k.infer_window_batch_encode(
        wp, x, _pad_to(sd, 0, _SAMPLE_BLOCK), _pad_to(tt, 0, _SAMPLE_BLOCK),
        n_steps=n_steps, threshold=threshold, leak=leak,
        block_b=_SAMPLE_BLOCK, block_n=bn, t_chunk=t_chunk,
        interpret=(backend == "interp"))
    return counts[:b, :n]


@functools.partial(jax.jit, static_argnames=("threshold", "leak", "t_chunk",
                                             "backend"))
def infer_window_batch(weights, spike_trains, *, threshold: int, leak: int,
                       t_chunk: int | None = None, backend: str = "ref"):
    """Serving path: spike counts int32[B, n] for B windows per launch.

    spike_trains: uint32[B, T, w]; weights frozen, membrane reset per
    sample (``reset_between_samples`` semantics).  ``t_chunk`` bounds
    the VMEM spike slab as in :func:`train_window_batch`.
    """
    if backend == "ref":
        return _ref.infer_window_batch_ref(weights, spike_trains,
                                           threshold, leak)
    n, _ = weights.shape
    t_steps = spike_trains.shape[1]
    bn = max(_block_n(max(8, n)), 8)
    wp = _pad_state(weights, bn)
    stp, tc = _pad_window(_pad_to(spike_trains, 2, _LANES), t_chunk)
    counts = _k.infer_window_batch(
        wp, stp, threshold=threshold, leak=leak, block_n=bn,
        t_chunk=tc, t_total=t_steps, interpret=(backend == "interp"))
    return counts[:, :n]
