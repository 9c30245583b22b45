"""Integration tests: encoder, preprocess, network execution, trainer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bitpack import pack, unpack
from repro.core.encoder import poisson_encode, poisson_encode_batch
from repro.core.lif import lif_params
from repro.core.preprocess import deskew, preprocess, soft_threshold
from repro.core.rvsnn import snn_regfile, snn_step
from repro.core.stdp import init_weights, stdp_params
from repro.core.trainer import SNNTrainConfig, accuracy, train
from repro.data.digits import make_digits
from repro.engine import (SNNEngine, SNNEnginePlan, reset_between_samples,
                          train_stream)


def test_poisson_rate_matches_intensity():
    x = jnp.array([0.0, 0.25, 0.5, 1.0] * 50)
    packed = poisson_encode(jax.random.key(0), x, 400)
    rates = unpack(packed, x.shape[0]).astype(np.float32).mean(axis=0)
    np.testing.assert_allclose(np.asarray(rates), np.asarray(x), atol=0.08)


def test_poisson_zero_and_one_are_deterministic():
    x = jnp.array([0.0, 1.0])
    packed = poisson_encode(jax.random.key(1), x, 64)
    bits = np.asarray(unpack(packed, 2))
    assert (bits[:, 0] == 0).all()
    assert (bits[:, 1] == 1).all()


def test_deskew_identity_on_symmetric():
    img = jnp.zeros((28, 28)).at[:, 13:15].set(1.0)
    out = np.asarray(deskew(img))
    np.testing.assert_allclose(out, np.asarray(img), atol=1e-3)


def test_deskew_straightens_shear():
    # Build a sheared vertical bar and check deskew concentrates columns.
    img = np.zeros((28, 28), np.float32)
    for y in range(28):
        x = int(13 + 0.4 * (y - 14))
        img[y, x] = 1.0
    out = np.asarray(deskew(jnp.asarray(img)))
    width = lambda im: (im.sum(axis=0) > 0.2).sum()
    assert width(out) < width(img)


def test_soft_threshold_zeroes_noise():
    img = jnp.array([[0.05, 0.2, 1.0]])
    out = np.asarray(soft_threshold(img, 0.1))
    assert out[0, 0] == 0.0
    assert 0.1 < out[0, 1] < 0.2
    assert abs(out[0, 2] - 1.0) < 1e-6


def test_inference_counts_bounded_and_deterministic():
    n, n_in, T = 8, 64, 32
    w = init_weights(n, 2, dense=True)
    key = jax.random.key(3)
    trains = poisson_encode_batch(
        key, jax.random.uniform(key, (4, n_in)), T)
    eng = SNNEngine(SNNEnginePlan(threshold=16, leak=1, w_exp=None))
    c1 = eng.infer(w, trains)
    c2 = eng.infer(w, trains)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    assert (np.asarray(c1) >= 0).all() and (np.asarray(c1) <= T).all()


def test_training_changes_weights_only_for_fired_rows():
    n, words, T = 4, 2, 16
    w0 = init_weights(n, words, dense=True)
    rf = snn_regfile(w0)
    trains = poisson_encode_batch(
        jax.random.key(5), jnp.full((2, 60), 0.8), T)
    # teacher: drive neuron 0, inhibit the rest hard
    teach = jnp.tile(jnp.array([[100, -10000, -10000, -10000]], jnp.int32),
                     (2, 1))
    eng = SNNEngine(SNNEnginePlan(threshold=8, leak=0, w_exp=16, gain=4,
                                  n_syn=60, ltp_prob=1023))
    rf2, counts = train_stream(eng, rf, trains, teach)
    w2 = np.asarray(rf2.weights)
    assert (w2[0] != np.asarray(w0)[0]).any()          # learned
    np.testing.assert_array_equal(w2[1:], np.asarray(w0)[1:])  # inhibited
    assert (np.asarray(counts)[:, 1:] == 0).all()


def test_homeostasis_prunes_to_budget():
    """After training, ON-counts sit near w_exp (paper §3.3)."""
    imgs, labels = make_digits(300, seed=11)
    cfg = SNNTrainConfig(n_neurons=10, w_exp=128, epochs=1, n_steps=48)
    model = train(cfg, imgs, labels)
    on = unpack(model.weights, 784).sum(axis=1)
    assert (np.asarray(on) < 128 * 2).all()
    assert (np.asarray(on) > 128 // 3).all()


@pytest.mark.slow
def test_end_to_end_learning_beats_chance():
    imgs, labels = make_digits(800, seed=21)
    timgs, tlabels = make_digits(200, seed=22)
    cfg = SNNTrainConfig(n_neurons=10, epochs=1)
    model = train(cfg, imgs, labels)
    st = poisson_encode_batch(jax.random.key(9), jnp.asarray(timgs),
                              cfg.n_steps)
    acc = accuracy(model, st, jnp.asarray(tlabels))
    assert acc > 0.35  # chance is 0.10


def test_window_and_step_paths_bit_exact():
    """The engine's window kernels == a per-cycle scan of
    ``rvsnn.snn_step``, full regfile: a training stream, then inference
    on the trained weights."""
    n, words, T, B = 12, 3, 20, 4
    w0 = init_weights(n, words, dense=False)
    lif = lif_params(40, 3)
    stdp = stdp_params(words * 32, w_exp=30, gain=4, ltp_prob=500)
    key = jax.random.key(31)
    trains = poisson_encode_batch(
        key, jax.random.uniform(key, (B, words * 32)), T)
    teach = jnp.asarray(
        np.random.default_rng(2).integers(-50, 50, (B, n), dtype=np.int32))
    rf = snn_regfile(w0)
    eng = SNNEngine(SNNEnginePlan(threshold=40, leak=3, w_exp=30, gain=4,
                                  n_syn=words * 32, ltp_prob=500))
    rf_w, c_w = train_stream(eng, rf, trains, teach)

    def sample(carry, inp):
        window, tch = inp
        carry, fired = jax.lax.scan(
            lambda c, x: snn_step(c, x, lif, stdp, tch),
            reset_between_samples(carry), window)
        return carry, jnp.sum(fired.astype(jnp.int32), axis=0)

    rf_s, c_s = jax.lax.scan(sample, rf, (trains, teach))
    for a, b in [(rf_w.weights, rf_s.weights), (rf_w.v, rf_s.v),
                 (rf_w.lfsr, rf_s.lfsr), (rf_w.spike, rf_s.spike),
                 (c_w, c_s)]:
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    i_w = eng.infer(rf_w.weights, trains)
    i_s = jax.vmap(lambda window: jnp.sum(jax.lax.scan(
        lambda c, x: snn_step(c, x, lif, None), snn_regfile(rf_w.weights),
        window)[1].astype(jnp.int32), axis=0))(trains)
    np.testing.assert_array_equal(np.asarray(i_w), np.asarray(i_s))


def test_reset_between_samples_clears_state():
    w = init_weights(3, 2)
    rf = snn_regfile(w)
    rf = rf._replace(v=jnp.array([5, 3, 1], jnp.int32),
                     spike=jnp.array([7, 7], jnp.uint32))
    rf2 = reset_between_samples(rf)
    assert (np.asarray(rf2.v) == 0).all()
    assert (np.asarray(rf2.spike) == 0).all()
    np.testing.assert_array_equal(np.asarray(rf2.weights), np.asarray(w))
