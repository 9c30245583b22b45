"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracle.

Sweeps shapes; integer kernels must be BIT-EXACT with ref.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lfsr
from repro.core.rvsnn import SnnRegFile
from repro.engine import SNNEngine, SNNEnginePlan
from repro.kernels import ops, ref

SHAPES = [(8, 1), (10, 25), (40, 25), (128, 32), (256, 130), (33, 7)]


def _rand_words(rng, shape):
    return jnp.asarray(rng.integers(0, 2**32, shape, dtype=np.uint32))


@pytest.mark.parametrize("n,w", SHAPES)
def test_spike_process_bit_exact(n, w):
    rng = np.random.default_rng(n * 100 + w)
    spikes = _rand_words(rng, (w,))
    weights = _rand_words(rng, (n, w))
    got = ops.spike_process(spikes, weights, backend="interp")
    want = ref.spike_process_ref(spikes, weights)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n", [8, 40, 100, 256])
@pytest.mark.parametrize("threshold,leak", [(10, 1), (192, 16), (1, 0)])
def test_lif_step_bit_exact(n, threshold, leak):
    rng = np.random.default_rng(n)
    v = jnp.asarray(rng.integers(0, 300, (n,), dtype=np.int32))
    c = jnp.asarray(rng.integers(-50, 120, (n,), dtype=np.int32))
    v2, f = ops.lif_step(v, c, threshold, leak, backend="interp")
    rv, rf = ref.lif_step_ref(v, c, threshold, leak)
    np.testing.assert_array_equal(np.asarray(v2), np.asarray(rv))
    np.testing.assert_array_equal(np.asarray(f), np.asarray(rf))


@pytest.mark.parametrize("n,w", SHAPES)
@pytest.mark.parametrize("wexp,ltp", [(128, 1023), (128, 16), (512, 64)])
def test_stdp_update_bit_exact(n, w, wexp, ltp):
    rng = np.random.default_rng(n * 7 + w)
    weights = _rand_words(rng, (n, w))
    pre = _rand_words(rng, (w,))
    fired = jnp.asarray(rng.integers(0, 2, (n,)).astype(bool))
    st = lfsr.seed(n + w, n * w).reshape(n, w)
    n_syn = w * 32
    got_w, got_s = ops.stdp_update(
        weights, pre, fired, st, w_exp=wexp, gain=4, n_syn=n_syn,
        ltp_prob=ltp, backend="interp")
    want_w, want_s = ref.stdp_update_ref(
        weights, pre, fired, st, wexp, 4, n_syn, ltp)
    np.testing.assert_array_equal(np.asarray(got_w), np.asarray(want_w))
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))


@pytest.mark.parametrize("n,w", [(10, 25), (128, 32), (40, 25)])
@pytest.mark.parametrize("train", [True, False])
def test_fused_snn_step_bit_exact(n, w, train):
    rng = np.random.default_rng(n + w)
    weights = _rand_words(rng, (n, w))
    pre = _rand_words(rng, (w,))
    v = jnp.asarray(rng.integers(0, 200, (n,), dtype=np.int32))
    teach = jnp.asarray(rng.integers(-100, 100, (n,), dtype=np.int32))
    st = lfsr.seed(5, n * w).reshape(n, w)
    kw = dict(threshold=192, leak=16, w_exp=128, gain=4, n_syn=w * 32,
              ltp_prob=16)
    got = ops.fused_snn_step(weights, pre, v, st, teach, train=train,
                             backend="interp", **kw)
    if train:
        want = ref.fused_snn_step_ref(weights, pre, v, st, teach, **kw)
    else:
        counts = ref.spike_process_ref(pre, weights) + teach
        v2, f = ref.lif_step_ref(v, counts, 192, 16)
        want = (weights, v2, f, st)
    for g, r in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


def _window_operands(n, w, t_steps, seed=0):
    rng = np.random.default_rng(seed)
    weights = _rand_words(rng, (n, w))
    spk = _rand_words(rng, (t_steps, w))
    v = jnp.asarray(rng.integers(0, 200, (n,), dtype=np.int32))
    teach = jnp.asarray(rng.integers(-100, 100, (n,), dtype=np.int32))
    st = lfsr.seed(n + w + t_steps, n * w).reshape(n, w)
    return weights, spk, v, teach, st


def _train_one(weights, spk, v, st, teach, *, t_chunk=None, **kw):
    """One stream's window through ``SNNEngine.train`` (the B=1 launch of
    ``train_window_batch``) in interpret mode: (weights', v', fired, lfsr')."""
    plan = SNNEnginePlan(kernel_backend="interp", t_chunk=t_chunk, **kw)
    rf = SnnRegFile(spike=jnp.zeros(spk.shape[1:], jnp.uint32), v=v,
                    lfsr=st, weights=weights)
    out = SNNEngine(plan).train(rf, spk, teach)
    return out.regfile.weights, out.regfile.v, out.fired, out.regfile.lfsr


def _infer_counts(weights, spk, *, t_chunk=None, threshold, leak, **_):
    """Counts of one window served by ``infer_window_batch`` (interp)."""
    return ops.infer_window_batch(weights, spk[None], threshold=threshold,
                                  leak=leak, t_chunk=t_chunk,
                                  backend="interp")[0]


@pytest.mark.parametrize("n,w", [(8, 1), (10, 25), (33, 7), (128, 32)])
@pytest.mark.parametrize("train", [True, False])
def test_fused_window_equals_sequential_steps(n, w, train):
    """Window kernel == T sequential fused steps, bit-exact incl. LFSR:
    learning through ``SNNEngine.train``; with the SU idle, the counts
    of the serving kernel from a reset membrane and no teacher."""
    t_steps = 9
    weights, spk, v, teach, st = _window_operands(n, w, t_steps)
    kw = dict(threshold=60, leak=4, w_exp=64, gain=4, n_syn=w * 32,
              ltp_prob=200)
    if not train:
        v, teach = jnp.zeros_like(v), jnp.zeros_like(teach)
    wq, vq, sq = weights, v, st
    raster = []
    for t in range(t_steps):
        wq, vq, f, sq = ops.fused_snn_step(
            wq, spk[t], vq, sq, teach, train=train, backend="ref", **kw)
        raster.append(np.asarray(f))
    if not train:
        got = _infer_counts(weights, spk, **kw)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.stack(raster).sum(axis=0))
        return
    got = _train_one(weights, spk, v, st, teach, **kw)
    want = (wq, vq, np.stack(raster), sq)
    for g, r in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


def _one_stream(*operands):
    return tuple(x[None] for x in operands)


def test_fused_window_zero_teach_matches_no_teach_ref():
    """teach=0 through the B=1 batched kernel == teach-free sequential
    reference."""
    n, w, t_steps = 10, 3, 7
    weights, spk, v, _, st = _window_operands(n, w, t_steps, seed=2)
    kw = dict(threshold=30, leak=2, w_exp=32, gain=4, n_syn=w * 32,
              ltp_prob=1023)
    got = ops.train_window_batch(
        *_one_stream(weights, spk, v, st, jnp.zeros((n,), jnp.int32)),
        backend="interp", **kw)
    want = ref.fused_snn_window_ref(weights, spk, v, st, None,
                                    kw["threshold"], kw["leak"],
                                    kw["w_exp"], kw["gain"], kw["n_syn"],
                                    kw["ltp_prob"])
    for g, r in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g[0]), np.asarray(r))


def test_fused_window_ref_matches_interp():
    """ops dispatch: ref and interp backends agree on the B=1 window."""
    n, w, t_steps = 40, 25, 12
    weights, spk, v, teach, st = _window_operands(n, w, t_steps, seed=4)
    kw = dict(threshold=50, leak=4, w_exp=128, gain=4, n_syn=w * 32,
              ltp_prob=16)
    operands = _one_stream(weights, spk, v, st, teach)
    a = ops.train_window_batch(*operands, backend="ref", **kw)
    b = ops.train_window_batch(*operands, backend="interp", **kw)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("n,w,b", [(8, 1, 2), (33, 7, 3), (128, 32, 4)])
def test_infer_window_batch_bit_exact(n, w, b):
    """Batched serving kernel == per-sample inference oracle."""
    rng = np.random.default_rng(n * 3 + w + b)
    weights = _rand_words(rng, (n, w))
    trains = _rand_words(rng, (b, 11, w))
    got = ops.infer_window_batch(weights, trains, threshold=40, leak=3,
                                 backend="interp")
    want = ref.infer_window_batch_ref(weights, trains, 40, 3)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and each batch row == the one-window oracle with the SU idle
    for i in range(b):
        _, _, fired, _ = ref.fused_snn_window_ref(
            weights, trains[i], jnp.zeros((n,), jnp.int32),
            jnp.ones((n, w), jnp.uint32), None, 40, 3, 0, 0, 1, 0,
            train=False)
        np.testing.assert_array_equal(
            np.asarray(got[i]),
            np.asarray(jnp.sum(fired.astype(jnp.int32), axis=0)))


@pytest.mark.parametrize("t_chunk", [1, 2, 4, 5, 9, 16])
@pytest.mark.parametrize("train", [True, False])
def test_chunked_window_equals_unchunked(t_chunk, train):
    """t_chunk-slab streaming == whole-window launch, bit-exact: learning
    through ``SNNEngine.train``, the SU idle through the serving kernel.

    Covers dividing chunks (1, 9), ragged tails (2, 4, 5) and
    t_chunk > T (16) at T=9.
    """
    n, w, t_steps = 33, 7, 9
    weights, spk, v, teach, st = _window_operands(n, w, t_steps, seed=6)
    kw = dict(threshold=60, leak=4, w_exp=64, gain=4, n_syn=w * 32,
              ltp_prob=200)
    if not train:
        want = _infer_counts(weights, spk, **kw)
        got = _infer_counts(weights, spk, t_chunk=t_chunk, **kw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        return
    want = _train_one(weights, spk, v, st, teach, **kw)
    got = _train_one(weights, spk, v, st, teach, t_chunk=t_chunk, **kw)
    for g, r in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


@pytest.mark.parametrize("t_chunk", [1, 3, 4, 11, 20])
def test_chunked_infer_batch_equals_unchunked(t_chunk):
    n, w, b, t_steps = 33, 7, 3, 11
    rng = np.random.default_rng(9)
    weights = _rand_words(rng, (n, w))
    trains = _rand_words(rng, (b, t_steps, w))
    want = ref.infer_window_batch_ref(weights, trains, 40, 3)
    got = ops.infer_window_batch(weights, trains, threshold=40, leak=3,
                                 t_chunk=t_chunk, backend="interp")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _batch_operands(b, n, w, t_steps, seed=0):
    rng = np.random.default_rng(seed)
    weights = _rand_words(rng, (b, n, w))
    spk = _rand_words(rng, (b, t_steps, w))
    v = jnp.asarray(rng.integers(0, 200, (b, n), dtype=np.int32))
    teach = jnp.asarray(rng.integers(-100, 100, (b, n), dtype=np.int32))
    st = jnp.stack([lfsr.seed(11 + 13 * i, n * w).reshape(n, w)
                    for i in range(b)])
    return weights, spk, v, teach, st


@pytest.mark.parametrize("n,w,b", [(8, 1, 2), (10, 25, 3), (33, 7, 2)])
@pytest.mark.parametrize("backend", ["ref", "interp"])
def test_train_window_batch_equals_sequential_streams(n, w, b, backend):
    """Batched training grid == B sequential windows, incl. each
    stream's LFSR sequence."""
    t_steps = 7
    weights, spk, v, teach, st = _batch_operands(b, n, w, t_steps, seed=3)
    kw = dict(threshold=60, leak=4, w_exp=64, gain=4, n_syn=w * 32,
              ltp_prob=200)
    got = ops.train_window_batch(weights, spk, v, st, teach,
                                 backend=backend, **kw)
    for i in range(b):
        want = ref.fused_snn_window_ref(
            weights[i], spk[i], v[i], st[i], teach[i], kw["threshold"],
            kw["leak"], kw["w_exp"], kw["gain"], kw["n_syn"],
            kw["ltp_prob"])
        for g, r in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g[i]),
                                          np.asarray(r))


@pytest.mark.parametrize("t_chunk", [2, 3, 7, 10])
def test_train_window_batch_chunked(t_chunk):
    """Batch grid + time chunking together stay bit-exact (ragged incl.)."""
    b, n, w, t_steps = 2, 10, 3, 7
    weights, spk, v, teach, st = _batch_operands(b, n, w, t_steps, seed=5)
    kw = dict(threshold=30, leak=2, w_exp=32, gain=4, n_syn=w * 32,
              ltp_prob=500)
    want = ops.train_window_batch(weights, spk, v, st, teach,
                                  backend="ref", **kw)
    got = ops.train_window_batch(weights, spk, v, st, teach,
                                 t_chunk=t_chunk, backend="interp", **kw)
    for g, r in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


def test_fused_equals_unfused_composition():
    """The fused kernel must equal SPU -> NU -> SU composition exactly."""
    rng = np.random.default_rng(0)
    n, w = 40, 25
    weights = _rand_words(rng, (n, w))
    pre = _rand_words(rng, (w,))
    v = jnp.zeros((n,), jnp.int32)
    teach = jnp.zeros((n,), jnp.int32)
    st = lfsr.seed(1, n * w).reshape(n, w)
    kw = dict(w_exp=128, gain=4, n_syn=800, ltp_prob=1023)
    counts = ops.spike_process(pre, weights, backend="interp")
    v2, f = ops.lif_step(v, counts, 50, 4, backend="interp")
    w2, s2 = ops.stdp_update(weights, pre, f, st, backend="interp", **kw)
    fw, fv, ff, fs = ops.fused_snn_step(
        weights, pre, v, st, teach, threshold=50, leak=4,
        backend="interp", **kw)
    np.testing.assert_array_equal(np.asarray(fw), np.asarray(w2))
    np.testing.assert_array_equal(np.asarray(fv), np.asarray(v2))
    np.testing.assert_array_equal(np.asarray(ff), np.asarray(f))
    np.testing.assert_array_equal(np.asarray(fs), np.asarray(s2))
