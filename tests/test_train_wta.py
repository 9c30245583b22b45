"""Lateral inhibition and adaptive thresholds in the encode train kernel:
the Pallas kernel (interpret mode) against ``kernels/ref.py``'s
``train_window_wta_ref``, θ through ``train_stream_batch`` and the
trainer's ``train_mode="wta"``, and the sharded train ops' refusal."""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lfsr
from repro.core.rvsnn import snn_regfile_batch
from repro.core.trainer import (MIN_SPIKES, SNNTrainConfig, train,
                                wta_chunk_step)
from repro.distributed import snn_mesh
from repro.engine import SNNEngine, SNNEnginePlan
from repro.engine.engine import train_stream_batch
from repro.kernels import ops


def _state(b, n, n_in, seed):
    rng = np.random.default_rng(seed)
    w = (n_in + 31) // 32
    weights = (rng.integers(0, 2**32, (b, n, w), dtype=np.uint32)
               & rng.integers(0, 2**32, (b, n, w), dtype=np.uint32))
    lf = jnp.stack([lfsr.seed(seed + i, n * w).reshape(n, w)
                    for i in range(b)])
    return jnp.asarray(weights), lf, rng


# (n, n_in, T, streams, samples, w_inh, theta_plus, t_chunk, threshold);
# neither inhibition nor θ: the w22a-shape test below.  The kernel's
# t_chunk (None: its own, a multiple of 32) is the block of cycles whose
# currents it computes with the weights frozen.
CASES = {
    "inhibition-theta-ragged-chunk": (40, 100, 11, 2, 3, 3, 1, 4, 6),
    "inhibition-no-theta": (256, 784, 7, 1, 2, 4, 0, None, 16),
    "theta-only-off-the-block": (200, 100, 6, 3, 2, 0, 2, None, 6),
    "same-group-consecutive-cycles": (128, 256, 24, 1, 2, 0, 0, None, 10),
    "several-groups-one-cycle": (384, 200, 16, 1, 2, 1, 1, None, 8),
    "spike-on-last-real-cycle-then-padding": (128, 256, 31, 1, 2, 0, 0,
                                              None, 10),
    "inputs-not-a-multiple-of-128": (128, 700, 12, 1, 2, 2, 1, None, 30),
    "last-group-partly-padding": (300, 256, 20, 2, 2, 2, 1, None, 10),
    "several-blocks-a-window": (256, 784, 70, 1, 2, 2, 1, 32, 40),
}


def _group_spikes(fired):
    """bool[B, T, n] raster -> bool[B, T, groups of 128]: a spike in the
    group at the cycle."""
    b, t, n = fired.shape
    f = np.pad(np.asarray(fired), ((0, 0), (0, 0), (0, (-n) % 128)))
    return f.reshape(b, t, -1, 128).any(axis=-1)


# what each case has to drive, on the reference's raster of some sample
DRIVES = {
    "same-group-consecutive-cycles":
        lambda f, tc: (_group_spikes(f)[:, 1:]
                       & _group_spikes(f)[:, :-1]).any(),
    "several-groups-one-cycle":
        lambda f, tc: (_group_spikes(f).sum(axis=-1) >= 2).any(),
    "spike-on-last-real-cycle-then-padding":
        lambda f, tc: f.shape[1] % 32 != 0 and f[:, -1].any(),
    "last-group-partly-padding":
        lambda f, tc: f[:, :, 256:].any(),
    "several-blocks-a-window":
        lambda f, tc: len({c // tc for c in np.nonzero(f.any(axis=(0, 2)))[0]
                           }) >= 2,
}


@pytest.mark.parametrize("case", CASES.values(), ids=CASES.keys())
def test_kernel_matches_reference_bit_for_bit(case, request):
    n, n_in, t, b, samples, w_inh, tp, tc, thr = case
    drive = DRIVES.get(request.node.callspec.id)
    driven = drive is None
    weights, lf, rng = _state(b, n, n_in, 5 + n)
    theta = jnp.asarray(rng.integers(0, 4, (b, n), dtype=np.int32))
    teach = jnp.zeros((b, n), jnp.int32)
    kw = dict(n_steps=t, threshold=thr, leak=1, w_exp=(n_in + 31) // 4,
              gain=4, n_syn=n_in, ltp_prob=300, w_inh=w_inh,
              theta_plus=tp)
    state = {"ref": (weights, lf, theta), "interp": (weights, lf, theta)}
    fired_any = 0
    for s in range(samples):
        x = jnp.asarray(rng.integers(0, 64, (b, n_in), dtype=np.uint8))
        seeds = jnp.asarray(rng.integers(0, 2**30, (b,), dtype=np.int32))
        v0 = jnp.zeros((b, n), jnp.int32)      # v resets between samples
        outs = {}
        for be, (w, st, th) in state.items():
            outs[be] = ops.train_window_batch_encode(
                w, x, seeds, v0, st, teach, backend=be, theta=th,
                t_chunk=tc if be == "interp" else None, **kw)
            state[be] = (outs[be][0], outs[be][3], outs[be][4])
        for g, r in zip(outs["interp"], outs["ref"]):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(r))
        fired_any += int(outs["ref"][2].sum())
        driven = driven or bool(drive(np.asarray(outs["ref"][2]), tc))
    assert fired_any > 0
    assert driven


def test_recomputes_count_the_groups_with_a_spike_each_cycle():
    """The kernel's ``recomputes`` is the number of (cycle, 128-neuron
    group) pairs with a spike in the raster it returns: 300 neurons (the
    last group partly padding), two blocks of 32 cycles."""
    b, n, n_in, t = 2, 300, 200, 40
    weights, lf, rng = _state(b, n, n_in, 17)
    x = jnp.asarray(rng.integers(0, 64, (b, n_in), dtype=np.uint8))
    seeds = jnp.asarray(rng.integers(0, 2**30, (b,), dtype=np.int32))
    zeros = jnp.zeros((b, n), jnp.int32)
    out = ops.train_window_batch_encode(
        weights, x, seeds, zeros, lf, zeros, n_steps=t, threshold=10,
        leak=1, w_exp=24, gain=4, n_syn=n_in, ltp_prob=300, theta=zeros,
        w_inh=1, theta_plus=1, t_chunk=32, backend="interp")
    fired = np.asarray(out[2])
    want = [sum(fired[s, c, g:g + 128].any() for c in range(t)
                for g in range(0, n, 128)) for s in range(b)]
    assert min(want) > 0
    np.testing.assert_array_equal(np.asarray(out[5]), want)


def test_without_inhibition_or_theta_it_is_todays_kernel():
    """The w22a shape (4 streams of 10 neurons, 784 inputs; T cut from
    72): the coupled body with w_inh = theta_plus = 0 and θ = 0 gives
    today's results, and θ stays 0."""
    b, n, n_in, t = 4, 10, 784, 12
    weights, lf, rng = _state(b, n, n_in, 3)
    x = jnp.asarray(rng.integers(0, 256, (b, n_in), dtype=np.uint8))
    seeds = jnp.asarray(rng.integers(0, 2**30, (b,), dtype=np.int32))
    v = jnp.zeros((b, n), jnp.int32)
    teach = jnp.asarray(rng.integers(-64, 64, (b, n), dtype=np.int32))
    kw = dict(n_steps=t, threshold=192, leak=16, w_exp=128, gain=4,
              n_syn=784, ltp_prob=jnp.asarray([16, 1023, 1023, 1023]),
              backend="interp")
    today = ops.train_window_batch_encode(weights, x, seeds, v, lf, teach,
                                          **kw)
    theta = jnp.zeros((b, n), jnp.int32)
    coupled = ops.train_window_batch_encode(weights, x, seeds, v, lf,
                                            teach, theta=theta, **kw)
    assert int(today[2].sum()) > 0
    for g, r in zip(coupled[:4], today):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))
    np.testing.assert_array_equal(np.asarray(coupled[4]), 0)


def _wta_plan(**kw):
    return SNNEnginePlan(threshold=10, leak=1, w_exp=24, gain=4, n_syn=100,
                         ltp_prob=300, encode="kernel", w_inh=3,
                         theta_plus=2, **kw)


def test_theta_carries_across_samples_and_calls_and_v_resets():
    n, n_in, t, samples = 40, 100, 10, 4
    weights, _, rng = _state(1, n, n_in, 11)
    x = jnp.asarray(rng.integers(0, 64, (1, samples, n_in), np.uint8))
    seeds = jnp.asarray(rng.integers(0, 2**30, (samples,), np.int32))
    eng = SNNEngine(_wta_plan(kernel_backend="ref"))
    step = jax.jit(functools.partial(train_stream_batch, eng, n_steps=t))
    rfs0 = snn_regfile_batch(weights, [7])
    rfs0 = rfs0._replace(v=jnp.full((1, n), 5, jnp.int32))  # reset away
    half, c1 = step(rfs0, intensities=x[:, :2], seeds=seeds[:2])
    two, c2 = step(half, intensities=x[:, 2:], seeds=seeds[2:])
    one, c = step(rfs0, intensities=x, seeds=seeds)
    np.testing.assert_array_equal(np.asarray(jnp.concatenate([c1, c2], 1)),
                                  np.asarray(c))
    for g, r in zip(two, one):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))
    # θ rose by theta_plus on every spike of every sample, over both calls
    np.testing.assert_array_equal(np.asarray(one.theta),
                                  2 * np.asarray(c.sum(axis=1)))
    assert int(c.sum()) > 0
    # each sample starts from v = 0: sample by sample through the op
    w, st, th = rfs0.weights, rfs0.lfsr, rfs0.theta
    for s in range(samples):
        w, v, fired, st, th, _ = ops.train_window_batch_encode(
            w, x[:, s], seeds[s], jnp.zeros((1, n), jnp.int32), st,
            jnp.zeros((1, n), jnp.int32), n_steps=t, threshold=10, leak=1,
            w_exp=24, gain=4, n_syn=100, ltp_prob=300, theta=th, w_inh=3,
            theta_plus=2)
        np.testing.assert_array_equal(np.asarray(fired.sum(axis=1)),
                                      np.asarray(c[:, s]))
    for g, r in zip((w, st, th, v), (one.weights, one.lfsr, one.theta,
                                     one.v)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


def test_wta_plan_needs_the_encode_kernel_path():
    with pytest.raises(ValueError, match="encode='kernel'"):
        SNNEnginePlan(w_inh=3)


@pytest.mark.parametrize("op", ["sharded_train_window_batch_encode",
                                "sharded_train_window_batch"])
def test_sharded_train_ops_refuse_inhibition(op):
    mesh = snn_mesh.snn_mesh()
    b, n, n_in, t = 2, 24, 96, 6
    weights, lf, rng = _state(b, n, n_in, 13)
    v = jnp.zeros((b, n), jnp.int32)
    teach = jnp.zeros((b, n), jnp.int32)
    kw = dict(threshold=20, leak=2, w_exp=24, gain=4, n_syn=n_in,
              ltp_prob=300)
    if op.endswith("encode"):
        x = jnp.asarray(rng.integers(0, 128, (b, n_in), np.uint8))
        args = (weights, x, jnp.arange(1, b + 1, dtype=jnp.int32), v, lf,
                teach)
        kw["n_steps"] = t
        unsharded = ops.train_window_batch_encode
    else:
        trains = jnp.asarray(rng.integers(0, 2**32, (b, t, 3), np.uint32))
        args = (weights, trains, v, lf, teach)
        unsharded = ops.train_window_batch
    sharded = getattr(snn_mesh, op)
    with pytest.raises(ValueError, match="lateral inhibition"):
        sharded(*args, mesh=mesh, theta=jnp.zeros((b, n), jnp.int32),
                w_inh=4, **kw)
    got = sharded(*args, mesh=mesh, w_inh=0, **kw)
    for g, r in zip(got, unsharded(*args, **kw)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


def test_trainer_wta_mode_trains_through_the_engine():
    rng = np.random.default_rng(0)
    n_samples, n_in = 8, 100
    imgs = (rng.random((n_samples, n_in), np.float32)
            * (rng.random((n_samples, n_in)) < 0.3))
    labels = rng.integers(0, 10, n_samples)
    cfg = SNNTrainConfig(n_inputs=n_in, n_neurons=40, n_steps=10,
                         threshold=8, leak=1, w_exp=20, gain=4,
                         ltp_prob=200, w_inh=3, theta_plus=1,
                         train_mode="wta", encode="kernel",
                         kernel_backend="ref", epochs=1)
    model = train(cfg, imgs, labels)
    assert model.weights.shape == (40, 4)
    assert model.neuron_class.shape == (40,)
    with pytest.raises(ValueError, match="encode='kernel'"):
        train(dataclasses.replace(cfg, encode="host"), imgs, labels)


def test_wta_chunk_step_counts_spikes_and_silent_samples():
    rng = np.random.default_rng(1)
    n, n_in, t, samples = 40, 100, 10, 6
    cfg = SNNTrainConfig(n_inputs=n_in, n_neurons=n, n_steps=t,
                         threshold=10, leak=1, w_exp=24, gain=4,
                         ltp_prob=300, w_inh=3, theta_plus=2,
                         train_mode="wta", encode="kernel",
                         kernel_backend="ref")
    weights, _, _ = _state(1, n, n_in, 11)
    rfs = snn_regfile_batch(weights, [7])
    x = jnp.asarray(rng.integers(0, 64, (samples, n_in), np.uint8))
    seeds = jnp.arange(samples, dtype=jnp.int32)
    got, spikes, silent, recomputes = wta_chunk_step(cfg)(rfs, x, seeds)
    eng = SNNEngine(cfg.plan())
    want, counts = train_stream_batch(eng, rfs, intensities=x[None],
                                      seeds=seeds, n_steps=t)
    per_sample = np.asarray(counts[0]).sum(axis=-1)
    assert int(spikes) == per_sample.sum() > 0
    assert int(silent) == (per_sample < MIN_SPIKES).sum()
    # 40 neurons are one group: a recompute in each cycle with a spike
    cycles, state = 0, rfs
    for s in range(samples):
        state, _, fired = eng.train_batch(
            state._replace(v=jnp.zeros_like(state.v)), intensities=x[None, s],
            seeds=seeds[s], n_steps=t)
        cycles += int(np.asarray(fired[0]).any(axis=-1).sum())
    assert int(recomputes) == cycles > 0
    np.testing.assert_array_equal(np.asarray(got.theta),
                                  np.asarray(want.theta))
