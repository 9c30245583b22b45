"""Batched training grid at the network/trainer level, and the keyed
trainer randomness fix."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.encoder import poisson_encode_batch
from repro.core.rvsnn import snn_regfile, snn_regfile_batch, snn_step
from repro.core.stdp import init_weights
from repro.core.trainer import SNNTrainConfig, _train_block, train
from repro.data.digits import make_digits
from repro.engine import (SNNEngine, SNNEnginePlan, train_stream,
                          train_stream_batch)


def _stream_operands(b, n, words, n_samples, t_steps):
    plan = SNNEnginePlan(threshold=40, leak=3, w_exp=30, gain=4,
                         n_syn=words * 32, ltp_prob=500)
    w0 = init_weights(n, words, dense=False)
    trains = jnp.stack([
        poisson_encode_batch(
            jax.random.key(40 + i),
            jax.random.uniform(jax.random.key(50 + i),
                               (n_samples, words * 32)), t_steps)
        for i in range(b)])
    teach = jnp.asarray(np.random.default_rng(2).integers(
        -50, 50, (b, n_samples, n), dtype=np.int32))
    return plan, w0, trains, teach


def test_train_stream_batch_matches_sequential_streams():
    """Each batched stream == a sequential train_stream run (weights,
    membrane, LFSR sequence and spike counts)."""
    b, n, words, n_samples, t_steps = 3, 12, 3, 4, 20
    plan, w0, trains, teach = _stream_operands(
        b, n, words, n_samples, t_steps)
    seeds = [101, 202, 303]
    rfs = snn_regfile_batch(jnp.broadcast_to(w0, (b, n, words)), seeds)
    eng = SNNEngine(plan)
    rfs2, counts = train_stream_batch(eng, rfs, trains, teach)
    for i in range(b):
        rf2, c2 = train_stream(eng, snn_regfile(w0, seed=seeds[i]),
                               trains[i], teach[i])
        np.testing.assert_array_equal(np.asarray(rfs2.weights[i]),
                                      np.asarray(rf2.weights))
        np.testing.assert_array_equal(np.asarray(rfs2.lfsr[i]),
                                      np.asarray(rf2.lfsr))
        np.testing.assert_array_equal(np.asarray(rfs2.v[i]),
                                      np.asarray(rf2.v))
        np.testing.assert_array_equal(np.asarray(counts[i]),
                                      np.asarray(c2))


def test_train_stream_batch_step_fallback_matches_window():
    """The batched window kernels == a per-cycle scan of
    ``rvsnn.snn_step`` over every stream's samples (v reset between
    samples)."""
    b, n, words, n_samples, t_steps = 2, 10, 2, 3, 12
    plan, w0, trains, teach = _stream_operands(
        b, n, words, n_samples, t_steps)
    rfs = snn_regfile_batch(jnp.broadcast_to(w0, (b, n, words)), [7, 9])
    rw, cw = train_stream_batch(SNNEngine(plan), rfs, trains, teach)

    def stream(rf, trains_b, teach_b):
        def sample(carry, inp):
            window, tch = inp
            carry, fired = jax.lax.scan(
                lambda c, x: snn_step(c, x, plan.lif(), plan.stdp(), tch),
                carry._replace(v=jnp.zeros_like(carry.v)), window)
            return carry, jnp.sum(fired.astype(jnp.int32), axis=0)

        return jax.lax.scan(sample, rf, (trains_b, teach_b))

    rs, cs = jax.vmap(stream)(rfs, trains, teach)
    for a, bb in [(rw.weights, rs.weights), (rw.v, rs.v),
                  (rw.lfsr, rs.lfsr), (cw, cs)]:
        np.testing.assert_array_equal(np.asarray(a), np.asarray(bb))


def test_train_stream_batch_interp_kernel_matches_ref():
    b, n, words, n_samples, t_steps = 2, 10, 2, 2, 9
    plan, w0, trains, teach = _stream_operands(
        b, n, words, n_samples, t_steps)
    rfs = snn_regfile_batch(jnp.broadcast_to(w0, (b, n, words)), [3, 5])
    rr, cr = train_stream_batch(SNNEngine(plan), rfs, trains, teach)
    ri, ci = train_stream_batch(
        SNNEngine(dataclasses.replace(plan, kernel_backend="interp",
                                      t_chunk=4)), rfs, trains, teach)
    for a, bb in [(rr.weights, ri.weights), (rr.v, ri.v),
                  (rr.lfsr, ri.lfsr), (cr, ci)]:
        np.testing.assert_array_equal(np.asarray(a), np.asarray(bb))


def test_parallel_train_mode_deterministic_and_shaped():
    imgs, labels = make_digits(80, seed=13)
    cfg = SNNTrainConfig(n_neurons=20, epochs=1, n_steps=16,
                         train_mode="parallel")
    m1 = train(cfg, imgs, labels)
    m2 = train(cfg, imgs, labels)
    assert m1.weights.shape == (20, cfg.words)
    np.testing.assert_array_equal(np.asarray(m1.neuron_class),
                                  np.tile(np.arange(10), 2))
    np.testing.assert_array_equal(np.asarray(m1.weights),
                                  np.asarray(m2.weights))


def test_parallel_blocks_decorrelated_by_keyed_seeds():
    """Parallel blocks share data + params; only keyed LFSR seeds differ,
    so their learned rows must differ."""
    imgs, labels = make_digits(80, seed=17)
    cfg = SNNTrainConfig(n_neurons=20, epochs=1, n_steps=16,
                         train_mode="parallel")
    m = train(cfg, imgs, labels)
    w = np.asarray(m.weights)
    assert (w[:10] != w[10:]).any()


def test_train_block_key_is_used_and_reproducible():
    """_train_block must thread its PRNG key into the regfile seeding:
    same key -> identical weights, different key -> different weights."""
    imgs, labels = make_digits(60, seed=19)
    cfg = SNNTrainConfig(n_neurons=10, epochs=1, n_steps=16)
    sp = poisson_encode_batch(jax.random.key(0),
                              jnp.asarray(imgs, jnp.float32), cfg.n_steps)
    labels_j = jnp.asarray(labels, jnp.int32)
    wa = _train_block(cfg, jax.random.key(1), labels_j, 0,
                      spike_trains=sp)
    wb = _train_block(cfg, jax.random.key(1), labels_j, 0,
                      spike_trains=sp)
    wc = _train_block(cfg, jax.random.key(2), labels_j, 0,
                      spike_trains=sp)
    np.testing.assert_array_equal(np.asarray(wa), np.asarray(wb))
    assert (np.asarray(wa) != np.asarray(wc)).any()
