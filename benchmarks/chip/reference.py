"""Plain reference of the Wenquxing 22A network, in ``jax.numpy``.

A copy of the architecture's semantics (``repro/core``: ``lif.py``,
``stdp.py``, ``lfsr.py``, ``encoder.encode_from_counter``), written out
here so that the yardstick imports nothing of the program.  Integer
arithmetic throughout, so agreement is equality.

* Encode: input ``i`` spikes at cycle ``t`` iff
  ``counter_hash(seed, t, i) & 0xFF < intensity[i]`` (P = x / 256).
* Spike process: count = popcount(spikes AND synapse row) per neuron.
* Streamlined LIF: ``v' = v + count (+ teach)``; fire iff
  ``v' >= threshold``; then ``v = 0`` if fired, else
  ``max(v' - leak, 0)``.  Cycles at or past a request's length change
  nothing.
* Binary stochastic STDP on a post-spike, per (neuron, 32-synapse word)
  lane of 16-bit LFSRs (taps 16, 14, 13, 11): two draws ``x1, x2``
  (low 10 bits); LTP ``w |= pre`` where ``x1 <= ltp_prob``; LTD
  ``w &= pre`` where ``x2 <= clip((popcount(row after LTP) - w_exp) *
  gain * 1024 // n_syn, 0, 1023)``; lanes of neurons that did not fire
  keep their weights and LFSR state.

Packed layout (the program's operand format): bit ``j`` of word ``k``
is input ``32 k + j``.

``in_bits`` is the control's knob: the encoder compares only the top
``in_bits`` bits of the draw and of the intensity (8 = as configured).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_PHI32 = 0x9E3779B9
_WEYL_IDX = 0x85EBCA6B
_MIX1 = 0x7FEB352D
_MIX2 = 0x846CA68B


def counter_hash(seed, cycle, idx):
    u = jnp.uint32
    h = (jnp.asarray(seed, u) + jnp.asarray(cycle, u) * u(_PHI32)
         + jnp.asarray(idx, u) * u(_WEYL_IDX))
    h = h ^ (h >> u(16))
    h = h * u(_MIX1)
    h = h ^ (h >> u(15))
    h = h * u(_MIX2)
    return h ^ (h >> u(16))


def pack(bits):
    """bool[..., n] -> uint32[..., ceil(n / 32)]."""
    n = bits.shape[-1]
    bits = jnp.pad(bits.astype(jnp.uint32),
                   [(0, 0)] * (bits.ndim - 1) + [(0, (-n) % 32)])
    bits = bits.reshape(bits.shape[:-1] + (-1, 32))
    return jnp.sum(bits << jnp.arange(32, dtype=jnp.uint32), axis=-1,
                   dtype=jnp.uint32)


def encode(seeds, intensities, t_steps: int, in_bits: int = 8):
    """uint32[R] seeds, uint8[R, n_in] -> packed spikes uint32[R, T, W]."""
    n_in = intensities.shape[-1]
    h = counter_hash(jnp.asarray(seeds, jnp.uint32)[:, None, None],
                     jnp.arange(t_steps, dtype=jnp.uint32)[None, :, None],
                     jnp.arange(n_in, dtype=jnp.uint32)[None, None, :])
    keep = jnp.uint32((0xFF << (8 - in_bits)) & 0xFF)
    draw = h & keep
    x = intensities.astype(jnp.uint32)[:, None, :] & keep
    return pack(draw < x)


def _popcount_and(pre, weights):
    """pre uint32[..., W] AND weights uint32[n, W] -> int32[..., n]."""
    both = pre[..., None, :] & weights
    return jnp.sum(jax.lax.population_count(both).astype(jnp.int32),
                   axis=-1)


def _lif(v, count, threshold, leak):
    v_int = v + count
    fired = v_int >= threshold
    return jnp.where(fired, 0, jnp.maximum(v_int - leak, 0)), fired


@functools.partial(jax.jit, static_argnames=("threshold", "leak"))
def infer_counts(weights, windows, t_total, *, threshold: int, leak: int):
    """Spike counts int32[R, n]: weights uint32[n, W], packed windows
    uint32[R, T, W], true lengths int32[R]; v starts at 0."""
    r, n = windows.shape[0], weights.shape[0]

    def cycle(carry, t):
        v, acc = carry
        v2, fired = _lif(v, _popcount_and(windows[:, t], weights),
                         threshold, leak)
        live = (t < t_total)[:, None]
        return (jnp.where(live, v2, v),
                acc + (fired & live).astype(jnp.int32)), None

    zeros = jnp.zeros((r, n), jnp.int32)
    (_, acc), _ = jax.lax.scan(cycle, (zeros, zeros),
                               jnp.arange(windows.shape[1]))
    return acc


@functools.partial(jax.jit, static_argnames=("t_steps", "threshold",
                                             "leak", "in_bits"))
def infer_counts_encoded(weights, intensities, seeds, t_total, *,
                         t_steps: int, threshold: int, leak: int,
                         in_bits: int = 8):
    """As :func:`infer_counts`, drawing each window from its intensities."""
    windows = encode(seeds, intensities, t_steps, in_bits)
    pad = weights.shape[-1] - windows.shape[-1]
    windows = jnp.pad(windows, ((0, 0), (0, 0), (0, pad)))
    return infer_counts(weights, windows, t_total, threshold=threshold,
                        leak=leak)


def lfsr_step(s):
    u = jnp.uint32
    fb = (s ^ (s >> u(2)) ^ (s >> u(3)) ^ (s >> u(5))) & u(1)
    return ((s >> u(1)) | (fb << u(15))) & u(0xFFFF)


def stdp(w, lfsr, pre, fired, *, ltp_prob, w_exp: int, gain: int,
         n_syn: int):
    """One SU update of one population: w, lfsr uint32[n, W]."""
    s1 = lfsr_step(lfsr)
    s2 = lfsr_step(s1)
    x_ltp, x_ltd = s1 & jnp.uint32(0x3FF), s2 & jnp.uint32(0x3FF)
    ltp = jnp.where(x_ltp <= ltp_prob, w | pre, w)
    pc = jnp.sum(jax.lax.population_count(ltp).astype(jnp.int32), axis=-1)
    prob = jnp.clip((pc - w_exp) * gain * 1024 // n_syn, 0, 1023)
    ltd = jnp.where(x_ltd <= prob.astype(jnp.uint32)[:, None], ltp & pre,
                    ltp)
    f = fired[:, None]
    return jnp.where(f, ltd, w), jnp.where(f, s2, lfsr)


@functools.partial(jax.jit, static_argnames=(
    "t_steps", "threshold", "leak", "w_exp", "gain", "n_syn", "in_bits"))
def train_streams(weights, lfsr, intensities, seeds, teach, ltp_prob, *,
                  t_steps: int, threshold: int, leak: int, w_exp: int,
                  gain: int, n_syn: int, in_bits: int = 8):
    """Online STDP of B independent populations over N samples.

    weights, lfsr uint32[B, n, W]; intensities uint8[N, n_in] and seeds
    uint32[N] (every stream sees the same samples); teach int32[B, N, n];
    ltp_prob uint32[B].  v resets to 0 before each sample.  Returns the
    final (weights, lfsr).
    """
    pad = weights.shape[-1]

    def sample(carry, inp):
        w, lf = carry
        x, sd, tch = inp
        win = encode(sd[None], x[None], t_steps, in_bits)[0]
        win = jnp.pad(win, ((0, 0), (0, pad - win.shape[-1])))

        def one_stream(w_b, lf_b, tch_b, lp_b):
            def cycle(c, pre):
                w_c, lf_c, v = c
                v2, fired = _lif(v, _popcount_and(pre, w_c) + tch_b,
                                 threshold, leak)
                w_c, lf_c = stdp(w_c, lf_c, pre, fired, ltp_prob=lp_b,
                                 w_exp=w_exp, gain=gain, n_syn=n_syn)
                return (w_c, lf_c, v2), None

            v0 = jnp.zeros(w_b.shape[:1], jnp.int32)
            (w_b, lf_b, _), _ = jax.lax.scan(cycle, (w_b, lf_b, v0), win)
            return w_b, lf_b

        return jax.vmap(one_stream)(w, lf, tch, ltp_prob), None

    (w, lf), _ = jax.lax.scan(sample, (weights, lfsr),
                              (intensities, seeds,
                               jnp.swapaxes(teach, 0, 1)))
    return w, lf
