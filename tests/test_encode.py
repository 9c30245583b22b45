"""In-kernel Poisson encode: the VMEM counter draw must be BIT-EXACT
with the ``encoder.encode_from_counter`` host oracle across every
dispatch path — ref/interp x {infer, train, train_batch} x
{unchunked, chunked, sharded} — and silent for zero intensity (the
property serving's batch padding rests on)."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lfsr
from repro.core.bitpack import unpack
from repro.core.encoder import (encode_from_counter,
                                encode_from_counter_batch,
                                quantize_intensities, spike_rate)
from repro.core.rvsnn import snn_regfile, snn_regfile_batch
from repro.distributed import snn_mesh
from repro.engine import SNNEngine, SNNEnginePlan
from repro.kernels import ops

N, W, T, B = 33, 7, 9, 3
N_IN = 200                      # < W * 32 = 224: exercises tail padding
KW = dict(threshold=60, leak=4, w_exp=64, gain=4, n_syn=N_IN,
          ltp_prob=200)


def _operands(seed=0):
    rng = np.random.default_rng(seed)
    weights = jnp.asarray(rng.integers(0, 2**32, (N, W), dtype=np.uint32))
    inten = jnp.asarray(rng.integers(0, 256, (B, N_IN), dtype=np.uint8))
    v = jnp.asarray(rng.integers(0, 200, (N,), dtype=np.int32))
    teach = jnp.asarray(rng.integers(-100, 100, (N,), dtype=np.int32))
    st = lfsr.seed(5, N * W).reshape(N, W)
    return weights, inten, v, teach, st


def _host_window(seed, inten, t_steps):
    win = encode_from_counter(seed, inten, t_steps)
    return jnp.pad(win, ((0, 0), (0, W - win.shape[1])))


# --- host oracle properties --------------------------------------------------


def test_counter_encode_rate_matches_intensity():
    inten = jnp.asarray([0, 64, 128, 255] * 50, jnp.uint8)
    bits = unpack(encode_from_counter(3, inten, 2048), inten.shape[0])
    rates = np.asarray(bits, np.float32).mean(axis=0).reshape(-1, 4)
    np.testing.assert_allclose(rates.mean(axis=0),
                               np.array([0, 64, 128, 255]) / 256,
                               atol=0.03)


def test_counter_encode_zero_intensity_is_silent():
    inten = jnp.zeros((96,), jnp.uint8)
    assert not np.asarray(encode_from_counter(11, inten, 64)).any()


def test_counter_encode_deterministic_and_seed_sensitive():
    inten = jnp.full((64,), 128, jnp.uint8)
    a = np.asarray(encode_from_counter(7, inten, 16))
    b = np.asarray(encode_from_counter(7, inten, 16))
    c = np.asarray(encode_from_counter(8, inten, 16))
    np.testing.assert_array_equal(a, b)
    assert (a != c).any()


def test_counter_encode_t0_slices_the_same_stream():
    """Any cycle range regenerates in isolation (the chunking and
    spike-register arguments rest on this)."""
    inten = jnp.asarray(np.random.default_rng(1).integers(
        0, 256, (70,), dtype=np.uint8))
    full = np.asarray(encode_from_counter(5, inten, 12))
    tail = np.asarray(encode_from_counter(5, inten, 3, t0=9))
    np.testing.assert_array_equal(full[9:], tail)


def test_quantize_intensities_round_trip_extremes():
    q = np.asarray(quantize_intensities(jnp.asarray([0.0, 0.5, 1.0])))
    np.testing.assert_array_equal(q, [0, 128, 255])


def test_spike_rate_popcount_per_time_slice():
    from repro.core.bitpack import pack
    rng = np.random.default_rng(2)
    n = 80
    bits = rng.integers(0, 2, (5, n))
    packed = pack(jnp.asarray(bits))
    np.testing.assert_allclose(np.asarray(spike_rate(packed, n)),
                               bits.mean(axis=1))


# --- op-level bit-exactness vs the host oracle -------------------------------


@pytest.mark.parametrize("backend", ["ref", "interp"])
@pytest.mark.parametrize("train", [True, False])
@pytest.mark.parametrize("t_chunk", [None, 4, 2])
def test_fused_window_encode_matches_host_oracle(backend, train, t_chunk):
    weights, inten, v, teach, st = _operands(3)
    got = ops.fused_snn_window_encode(
        weights, inten[0], 7, v, st, teach, n_steps=T, train=train,
        t_chunk=t_chunk, backend=backend, **KW)
    want = ops.fused_snn_window(
        weights, _host_window(7, inten[0], T), v, st, teach, train=train,
        t_chunk=t_chunk, backend=backend, **KW)
    for g, r in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


@pytest.mark.parametrize("backend", ["ref", "interp"])
@pytest.mark.parametrize("t_chunk", [None, 4])
def test_train_batch_encode_matches_host_oracle(backend, t_chunk):
    weights, inten, _, _, _ = _operands(4)
    rng = np.random.default_rng(4)
    wts = jnp.asarray(rng.integers(0, 2**32, (B, N, W), dtype=np.uint32))
    vb = jnp.asarray(rng.integers(0, 200, (B, N), dtype=np.int32))
    tb = jnp.asarray(rng.integers(-100, 100, (B, N), dtype=np.int32))
    stb = jnp.stack([lfsr.seed(11 + i, N * W).reshape(N, W)
                     for i in range(B)])
    seeds = jnp.asarray([3, 9, 27], jnp.int32)
    lp = jnp.asarray([16, 500, 1023], jnp.int32)
    kw = {k: v for k, v in KW.items() if k != "ltp_prob"}
    got = ops.train_window_batch_encode(
        wts, inten, seeds, vb, stb, tb, n_steps=T, ltp_prob=lp,
        t_chunk=t_chunk, backend=backend, **kw)
    wins = encode_from_counter_batch(seeds, inten, T)
    wins = jnp.pad(wins, ((0, 0), (0, 0), (0, W - wins.shape[2])))
    want = ops.train_window_batch(
        wts, wins, vb, stb, tb, ltp_prob=lp, t_chunk=t_chunk,
        backend=backend, **kw)
    for g, r in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


@pytest.mark.parametrize("backend", ["ref", "interp"])
@pytest.mark.parametrize("t_chunk", [None, 4])
def test_infer_batch_encode_ragged_matches_host_oracle(backend, t_chunk):
    """Per-sample t_total (SMEM-masked in kernel, zero-masked on host)
    returns the counts of serving each sample at its true length."""
    weights, inten, _, _, _ = _operands(5)
    seeds = jnp.asarray([1, 2, 3], jnp.int32)
    tt = [T, 5, 2]
    got = ops.infer_window_batch_encode(
        weights, inten, seeds, n_steps=T, threshold=60, leak=4,
        t_total=jnp.asarray(tt), t_chunk=t_chunk, backend=backend)
    for i, t_i in enumerate(tt):
        want = ops.infer_window_batch(
            weights, _host_window(seeds[i], inten[i], t_i)[None],
            threshold=60, leak=4, backend=backend)[0]
        np.testing.assert_array_equal(np.asarray(got[i]),
                                      np.asarray(want))


# --- the serving kernel's MXU path against the integer oracle ---------------

# (neurons, batch, inputs, window, t_chunk): 40 and 300 neurons (300 is
# no multiple of the 128-neuron block), 512 (two blocks of 256), batches
# of 3, 32 and 35 (two sample blocks), 784 and 100 inputs, the whole
# window or chunks that leave a ragged tail
_MXU_CASES = {
    "n40-b3-in784": (40, 3, 784, 16, None),
    "n40-b32-in784-chunk5": (40, 32, 784, 16, 5),
    "n300-b3-in100-chunk5": (300, 3, 100, 12, 5),
    "n300-b32-in100": (300, 32, 100, 9, None),
    "n512-b35-in784-chunk3": (512, 35, 784, 8, 3),
}


@pytest.mark.parametrize("case", list(_MXU_CASES))
def test_infer_batch_encode_mxu_matches_integer_oracle(case):
    """Counts of the MXU serving kernel (interpret mode) equal the plain
    integer oracle's: ragged lengths down to 1, intensity rows of 0
    (silent) and 255, seeds near 2**31 - 1."""
    from repro.kernels import ref

    n, b, n_in, t_steps, t_chunk = _MXU_CASES[case]
    rng = np.random.default_rng(n + b + n_in)
    words = -(-n_in // 32)
    weights = jnp.asarray(
        rng.integers(0, 2**32, (n, words), dtype=np.uint32)
        & rng.integers(0, 2**32, (n, words), dtype=np.uint32))
    inten = rng.integers(0, 256, (b, n_in), dtype=np.uint8)
    inten[0], inten[1] = 255, 0
    seeds = jnp.asarray(2**31 - 1 - np.arange(b), jnp.int32)
    tt = jnp.asarray([t_steps, 1] + [t_steps - i % t_steps
                                     for i in range(b - 2)], jnp.int32)
    lif = dict(threshold=192, leak=16) if n_in == 784 else \
        dict(threshold=60, leak=4)
    got = np.asarray(ops.infer_window_batch_encode(
        weights, jnp.asarray(inten), seeds, n_steps=t_steps, t_total=tt,
        t_chunk=t_chunk, backend="interp", **lif))
    want = np.asarray(ref.infer_window_batch_encode_ref(
        weights, jnp.asarray(inten), seeds, t_steps, t_total=tt, **lif))
    np.testing.assert_array_equal(got, want)
    assert got.shape == (b, n)
    assert not got[1].any() and got[0].any()


def test_encode_sharded_matches_unsharded_local_mesh():
    mesh = snn_mesh.snn_mesh()
    weights, inten, v, teach, st = _operands(6)
    seeds = jnp.asarray([4, 5, 6], jnp.int32)
    got = snn_mesh.sharded_infer_window_batch_encode(
        weights, inten, seeds, n_steps=T, threshold=60, leak=4,
        mesh=mesh)
    want = ops.infer_window_batch_encode(
        weights, inten, seeds, n_steps=T, threshold=60, leak=4)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for train in (True, False):
        got = snn_mesh.sharded_fused_snn_window_encode(
            weights, inten[0], 7, v, st, teach, n_steps=T, train=train,
            mesh=mesh, **KW)
        want = ops.fused_snn_window_encode(
            weights, inten[0], 7, v, st, teach, n_steps=T, train=train,
            **KW)
        for g, r in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


# --- engine verbs: encode placement is invisible to results ------------------


def _plans(**over):
    base = dict(KW, encode_seed=42, **over)
    return (SNNEnginePlan(**base, encode="host"),
            SNNEnginePlan(**base, encode="kernel"))


@pytest.mark.parametrize("kb,t_chunk", [("ref", None), ("interp", 5)])
def test_engine_verbs_host_vs_kernel_encode(kb, t_chunk):
    weights, inten, _, teach, _ = _operands(7)
    rng = np.random.default_rng(7)
    teach_b = jnp.asarray(rng.integers(-50, 50, (B, N), dtype=np.int32))
    ph, pk = _plans(kernel_backend=kb, t_chunk=t_chunk)
    eh, ek = SNNEngine(ph), SNNEngine(pk)

    tt = jnp.asarray([T, 7, 3])
    np.testing.assert_array_equal(
        np.asarray(eh.infer(weights, intensities=inten, n_steps=T,
                            t_total=tt)),
        np.asarray(ek.infer(weights, intensities=inten, n_steps=T,
                            t_total=tt)))

    rf = snn_regfile(weights, seed=9)
    oa = eh.train(rf, intensities=inten[0], teach=teach, n_steps=T)
    ob = ek.train(rf, intensities=inten[0], teach=teach, n_steps=T)
    for x, y in zip(oa.regfile, ob.regfile):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    np.testing.assert_array_equal(np.asarray(oa.fired),
                                  np.asarray(ob.fired))

    rfs = snn_regfile_batch(
        jnp.asarray(rng.integers(0, 2**32, (B, N, W), dtype=np.uint32)),
        [1, 2, 3])
    ra, ca, fa = eh.train_batch(rfs, intensities=inten, teach=teach_b,
                                n_steps=T)
    rb, cb, fb = ek.train_batch(rfs, intensities=inten, teach=teach_b,
                                n_steps=T)
    for x, y in zip(ra, rb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    np.testing.assert_array_equal(np.asarray(ca), np.asarray(cb))
    np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))


def test_train_batch_accepts_omitted_teach():
    """teach=None (now that the signature allows it) means zero teacher
    current on every path, same as train()."""
    weights, inten, _, _, _ = _operands(9)
    rng = np.random.default_rng(9)
    wts = jnp.asarray(rng.integers(0, 2**32, (B, N, W), dtype=np.uint32))
    rfs = snn_regfile_batch(wts, [4, 5, 6])
    ph, pk = _plans()
    for eng in (SNNEngine(ph), SNNEngine(pk)):
        rfs2, counts, _ = eng.train_batch(rfs, intensities=inten,
                                          n_steps=T)
        want = eng.train_batch(rfs, intensities=inten, n_steps=T,
                               teach=jnp.zeros((B, N), jnp.int32))
        np.testing.assert_array_equal(np.asarray(counts),
                                      np.asarray(want[1]))


def test_engine_rejects_ambiguous_inputs():
    weights, inten, _, _, _ = _operands(8)
    eng = SNNEngine(SNNEnginePlan(**KW))
    with pytest.raises(ValueError):
        eng.infer(weights)                       # neither form
    with pytest.raises(ValueError):
        eng.infer(weights, intensities=inten)    # missing n_steps
    with pytest.raises(ValueError):
        eng.infer(weights, jnp.zeros((B, T, W), jnp.uint32),
                  intensities=inten, n_steps=T)  # both forms


def test_plan_encode_validation():
    with pytest.raises(ValueError):
        SNNEnginePlan(encode="vmem")
    with pytest.raises(ValueError):
        SNNEnginePlan(encode="kernel", cycle_backend="step")
    assert SNNEnginePlan(encode="kernel").encode_seed == 0
    cfg_plan = dataclasses.replace(SNNEnginePlan(), encode_seed=7)
    assert cfg_plan.encode == "host"
