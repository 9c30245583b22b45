"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantic ground truth: kernel tests sweep shapes/dtypes and
assert bit-exact (integer kernels) or allclose (attention) agreement.
The SNN oracles delegate to ``repro.core`` — the core module IS the
architectural reference (ISA-level semantics); the kernels are the TPU
microarchitecture.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import lfsr as _lfsr
from repro.core.bitpack import popcount
from repro.core.lif import LIFParams, lif_step as _lif_step
from repro.core.stdp import STDPParams, stdp_update as _stdp_update


def spike_process_ref(spikes: jnp.ndarray, weights: jnp.ndarray
                      ) -> jnp.ndarray:
    """SPU: valid-spike counts.  spikes u32[w], weights u32[n, w] -> i32[n]."""
    return popcount(jnp.bitwise_and(spikes[None, :], weights))


def lif_step_ref(v: jnp.ndarray, count: jnp.ndarray, threshold: int,
                 leak: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """NU: streamlined LIF.  v,count i32[n] -> (v' i32[n], fired bool[n])."""
    return _lif_step(v, count, LIFParams(jnp.int32(threshold),
                                         jnp.int32(leak)))


def stdp_update_ref(weights, pre_spikes, post_fired, lfsr_state,
                    w_exp: int, gain: int, n_syn: int, ltp_prob: int):
    """SU: binary stochastic STDP row update (see repro.core.stdp)."""
    p = STDPParams(jnp.int32(w_exp), jnp.int32(gain), jnp.int32(n_syn),
                   jnp.uint32(ltp_prob))
    return _stdp_update(weights, pre_spikes, post_fired, lfsr_state, p)


def fused_snn_step_ref(weights, pre_spikes, v, lfsr_state, teach,
                       threshold: int, leak: int, w_exp: int, gain: int,
                       n_syn: int, ltp_prob: int, train: bool = True):
    """SNNU: one fused spike->neuron->synapse cycle.

    Returns (weights', v', fired, lfsr').  ``teach`` may be None;
    ``train=False`` leaves the SU idle (weights/LFSR pass through).
    """
    counts = spike_process_ref(pre_spikes, weights)
    if teach is not None:
        counts = counts + teach
    v2, fired = lif_step_ref(v, counts, threshold, leak)
    if not train:
        return weights, v2, fired, lfsr_state
    w2, lf2 = stdp_update_ref(weights, pre_spikes, fired, lfsr_state,
                              w_exp, gain, n_syn, ltp_prob)
    return w2, v2, fired, lf2


def fused_snn_window_ref(weights, spike_train, v, lfsr_state, teach,
                         threshold: int, leak: int, w_exp: int, gain: int,
                         n_syn: int, ltp_prob: int, train: bool = True):
    """T sequential fused SNNU cycles (the window kernel's ground truth).

    spike_train: uint32[T, w].  Returns (weights', v', fired bool[T, n],
    lfsr') — bit-exact (incl. the LFSR sequence) with T sequential
    :func:`fused_snn_step_ref` calls.
    """

    def body(carry, pre):
        w, vv, st = carry
        w2, v2, fired, st2 = fused_snn_step_ref(
            w, pre, vv, st, teach, threshold, leak, w_exp, gain,
            n_syn, ltp_prob, train)
        return (w2, v2, st2), fired

    (w2, v2, st2), fired = jax.lax.scan(
        body, (weights, v, lfsr_state), spike_train)
    return w2, v2, fired, st2


def train_window_batch_ref(weights, spike_trains, v, lfsr_state, teach,
                           threshold: int, leak: int, w_exp: int,
                           gain: int, n_syn: int, ltp_prob):
    """B independent training streams (the batched train kernel's oracle).

    weights/lfsr u32[B, n, w], spike_trains u32[B, T, w], v i32[B, n],
    teach i32[B, n]; ltp_prob is a shared int or a per-stream i32[B]
    vector (mirroring the kernel's SMEM scalar operand).  Each stream is
    exactly one :func:`fused_snn_window_ref` run — bit-exact (incl. each
    stream's LFSR sequence) with B sequential single-stream windows.
    Returns (weights', v', fired bool[B, T, n], lfsr').
    """
    b = weights.shape[0]
    lp = jnp.broadcast_to(jnp.asarray(ltp_prob, jnp.int32), (b,))

    def one(w, s, vv, st, tc, lp_b):
        return fused_snn_window_ref(w, s, vv, st, tc, threshold, leak,
                                    w_exp, gain, n_syn, lp_b, True)

    return jax.vmap(one)(weights, spike_trains, v, lfsr_state, teach, lp)


def _host_windows(seeds, intensities, n_steps: int, words: int,
                  t_total=None) -> jnp.ndarray:
    """Host counter encode shaped for the kernels (the encode oracles'
    ground truth); see :func:`repro.core.encoder.encode_windows_host`."""
    from repro.core.encoder import encode_windows_host

    return encode_windows_host(seeds, intensities, n_steps, words,
                               t_total)


def fused_snn_window_encode_ref(weights, intensities, seed, v, lfsr_state,
                                teach, n_steps: int, threshold: int,
                                leak: int, w_exp: int, gain: int,
                                n_syn: int, ltp_prob: int,
                                train: bool = True):
    """Encode-fused window oracle: host-encode, then the window oracle."""
    win = _host_windows(seed, intensities[None], n_steps,
                        weights.shape[1])[0]
    return fused_snn_window_ref(weights, win, v, lfsr_state, teach,
                                threshold, leak, w_exp, gain, n_syn,
                                ltp_prob, train)


def train_window_wta_ref(weights, spike_train, v, lfsr_state, teach, theta,
                         threshold: int, leak: int, w_exp: int, gain: int,
                         n_syn: int, ltp_prob, w_inh: int,
                         theta_plus: int):
    """One population over T cycles with lateral inhibition and an
    adaptive threshold per neuron (Diehl & Cook's excitatory layer, each
    inhibitory neuron a one-cycle relay of its partner).  Per cycle t,
    with f(t-1) the last cycle's spikes (0 before the first):

        I_j   = popcount(pre(t) & w_j) + teach_j
        inh_j = w_inh * (F(t-1) - f_j(t-1)),  F = sum_k f_k
        u_j   = v_j + I_j - inh_j
        f_j   = u_j >= threshold + θ_j
        v_j   = 0 if f_j else max(u_j - leak, 0)
        θ_j  += theta_plus * f_j
        w, lfsr <- STDP(w, pre(t), f(t), lfsr)    (the unchanged rule)

    With ``w_inh = 0`` and θ = 0 throughout (``theta_plus = 0``) this is
    :func:`fused_snn_window_ref`.  Returns (weights', v', fired bool[T, n],
    lfsr', theta').
    """

    def body(carry, pre):
        w, vv, st, th, prev = carry
        inh = w_inh * (jnp.sum(prev) - prev)
        counts = spike_process_ref(pre, w) + teach - inh
        v2, fired = _lif_step(vv, counts, LIFParams(threshold + th,
                                                    jnp.int32(leak)))
        spikes = fired.astype(jnp.int32)
        w2, st2 = stdp_update_ref(w, pre, fired, st, w_exp, gain, n_syn,
                                  ltp_prob)
        return (w2, v2, st2, th + theta_plus * spikes, spikes), fired

    zeros = jnp.zeros_like(v)
    (w2, v2, st2, th2, _), fired = jax.lax.scan(
        body, (weights, v, lfsr_state, theta, zeros), spike_train)
    return w2, v2, fired, st2, th2


def train_window_batch_encode_ref(weights, intensities, seeds, v,
                                  lfsr_state, teach, n_steps: int,
                                  threshold: int, leak: int, w_exp: int,
                                  gain: int, n_syn: int, ltp_prob,
                                  theta=None, w_inh: int = 0,
                                  theta_plus: int = 0):
    """Encode-fused batched training oracle; with ``theta`` i32[B, n],
    each stream is one :func:`train_window_wta_ref` population, and θ'
    and :func:`wta_recomputes_ref` of its raster are a fifth and a sixth
    result."""
    wins = _host_windows(seeds, intensities, n_steps, weights.shape[2])
    if theta is None:
        return train_window_batch_ref(weights, wins, v, lfsr_state, teach,
                                      threshold, leak, w_exp, gain, n_syn,
                                      ltp_prob)
    b = weights.shape[0]
    lp = jnp.broadcast_to(jnp.asarray(ltp_prob, jnp.int32), (b,))

    def one(w, s, vv, st, tc, th, lp_b):
        return train_window_wta_ref(w, s, vv, st, tc, th, threshold, leak,
                                    w_exp, gain, n_syn, lp_b, w_inh,
                                    theta_plus)

    out = jax.vmap(one)(weights, wins, v, lfsr_state, teach, theta, lp)
    return out + (wta_recomputes_ref(out[2]),)


def wta_recomputes_ref(fired) -> jnp.ndarray:
    """The (cycle, 128-neuron group) pairs with a spike in each stream's
    raster bool[B, T, n]: i32[B], the coupled train kernel's count of
    STDP updates, each followed by a recompute of the group's input
    currents."""
    b, t, n = fired.shape
    f = jnp.pad(fired, ((0, 0), (0, 0), (0, (-n) % 128)))
    return jnp.sum(jnp.any(f.reshape(b, t, -1, 128), axis=-1),
                   axis=(1, 2), dtype=jnp.int32)


def infer_window_batch_encode_ref(weights, intensities, seeds,
                                  n_steps: int, threshold: int,
                                  leak: int, t_total=None):
    """Encode-fused serving oracle (ragged lengths via ``t_total``).

    Count-equality with the kernel's SMEM masking holds for any
    ``threshold >= 1``: a zero-masked cycle adds no input counts and the
    membrane only leaks, so it cannot fire (the kernel freezes v instead
    of leaking it, but v is discarded here).
    """
    wins = _host_windows(seeds, intensities, n_steps, weights.shape[1],
                         t_total)
    return infer_window_batch_ref(weights, wins, threshold, leak)


def infer_window_batch_ref(weights, spike_trains, threshold: int,
                           leak: int):
    """Serving oracle: spike counts int32[B, n], weights frozen, v reset."""
    n = weights.shape[0]

    def one(train):
        def body(vv, pre):
            counts = spike_process_ref(pre, weights)
            v2, fired = lif_step_ref(vv, counts, threshold, leak)
            return v2, fired

        _, fired = jax.lax.scan(body, jnp.zeros((n,), jnp.int32), train)
        return jnp.sum(fired.astype(jnp.int32), axis=0)

    return jax.vmap(one)(spike_trains)


def attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  causal: bool = True, window: int | None = None,
                  scale: float | None = None) -> jnp.ndarray:
    """Dense reference attention.

    q: [B, Hq, Tq, D]; k, v: [B, Hkv, Tk, D] (GQA: Hq % Hkv == 0).
    window: sliding-window size (keys within [i - window + 1, i]).
    """
    b, hq, tq, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    qg = qf.reshape(b, hkv, group, tq, d)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kf)
    # offset: queries are the LAST tq positions of the tk-long stream
    tk = kf.shape[2]
    qpos = jnp.arange(tq)[:, None] + (tk - tq)
    kpos = jnp.arange(tk)[None, :]
    mask = jnp.ones((tq, tk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, vf)
    return o.reshape(b, hq, tq, d).astype(q.dtype)
