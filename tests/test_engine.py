"""Engine bit-exactness: the verbs against a per-cycle scan of
``rvsnn.snn_step`` and against each other (one stream / batch /
sharded), plan validation, and the per-stream ``ltp_prob`` schedule."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lfsr
from repro.core.rvsnn import snn_regfile, snn_regfile_batch, snn_step
from repro.core.trainer import SNNTrainConfig, train
from repro.data.digits import make_digits
from repro.distributed import snn_mesh
from repro.engine import (SNNEngine, SNNEnginePlan, plan_from_config,
                          train_stream, train_stream_batch)
from repro.kernels import ops

N, W, T, B = 24, 5, 12, 3
KW = dict(threshold=40, leak=3, w_exp=30, gain=4, n_syn=W * 32,
          ltp_prob=500)


def _plan(**over):
    return SNNEnginePlan(**{**KW, **over})


def _operands(seed=0):
    rng = np.random.default_rng(seed)
    weights = jnp.asarray(rng.integers(0, 2**32, (N, W), dtype=np.uint32))
    windows = jnp.asarray(
        rng.integers(0, 2**32, (B, T, W), dtype=np.uint32))
    teach = jnp.asarray(rng.integers(-50, 50, (N,), dtype=np.int32))
    return weights, windows, teach


def _assert_rf_equal(a, b):
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _scan_window(plan, rf, window, teach=None):
    """One window as a per-cycle scan of ``rvsnn.snn_step`` (the
    architectural reference): (rf', counts i32[n], fired bool[T, n])."""
    rf2, fired = jax.lax.scan(
        lambda c, x: snn_step(c, x, plan.lif(), plan.stdp(), teach),
        rf, window)
    return rf2, jnp.sum(fired.astype(jnp.int32), axis=0), fired


def _scan_stream(plan, rf, trains, teach):
    """A sample stream of :func:`_scan_window`, v and the spike register
    reset before each sample: (rf', counts i32[N, n])."""
    def sample(carry, inp):
        carry = carry._replace(v=jnp.zeros_like(carry.v),
                               spike=jnp.zeros_like(carry.spike))
        carry, counts, _ = _scan_window(plan, carry, *inp)
        return carry, counts

    return jax.lax.scan(sample, rf, (trains, teach))


# --- plan validation ---------------------------------------------------------

def test_plan_validation():
    with pytest.raises(ValueError):
        _plan(kernel_backend="cuda")
    with pytest.raises(ValueError):
        _plan(t_chunk=0)
    with pytest.raises(ValueError):
        _plan(max_batch=0)
    assert not _plan(w_exp=None).learn
    assert _plan().learn


def test_plan_from_config_active_schedule():
    cfg = SNNTrainConfig(ltp_prob=16, ltp_prob_active=1023)
    assert plan_from_config(cfg).ltp_prob == 16
    assert plan_from_config(cfg, block_idx=1).ltp_prob == 1023
    assert plan_from_config(cfg).n_syn == cfg.n_inputs


# --- infer: ref / interp / inference-only plan vs the per-cycle scan -------

def test_infer_parity_all_paths():
    weights, windows, _ = _operands()
    rf0 = snn_regfile(weights)
    want = jax.vmap(
        lambda win: _scan_window(_plan(w_exp=None), rf0, win)[1])(windows)
    for plan in (_plan(), _plan(w_exp=None),
                 _plan(kernel_backend="interp", t_chunk=5)):
        got = SNNEngine(plan).infer(weights, windows)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --- train: ref / interp vs the per-cycle scan; inference-only plans -------

def test_train_parity_window_vs_step_vs_legacy():
    weights, windows, teach = _operands(1)
    rf = snn_regfile(weights, seed=9)
    want_rf, want_c, want_f = _scan_window(_plan(), rf, windows[0], teach)
    for plan in (_plan(), _plan(kernel_backend="interp", t_chunk=5)):
        out = SNNEngine(plan).train(rf, windows[0], teach)
        _assert_rf_equal(out.regfile, want_rf)
        np.testing.assert_array_equal(np.asarray(out.spike_counts),
                                      np.asarray(want_c))
        np.testing.assert_array_equal(np.asarray(out.fired),
                                      np.asarray(want_f))


def test_train_su_idle_matches_legacy_inference():
    """An inference-only plan (``w_exp=None``) no longer trains with the
    SU idle: ``train`` refuses it as ``train_batch`` does, and ``infer``
    gives the counts of the SU-idle per-cycle scan."""
    weights, windows, _ = _operands(2)
    plan = _plan(w_exp=None)
    rf = snn_regfile(weights, seed=4)
    with pytest.raises(ValueError, match="learning plan"):
        SNNEngine(plan).train(rf, windows[0])
    _, want, _ = _scan_window(plan, rf, windows[0])
    got = SNNEngine(plan).infer(weights, windows[:1])
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want))


# --- train_batch: batched grid vs one-stream launches / the scan -----------

def test_train_batch_parity_sequential_and_step():
    weights, windows, _ = _operands(3)
    rng = np.random.default_rng(7)
    wts_b = jnp.asarray(rng.integers(0, 2**32, (B, N, W),
                                     dtype=np.uint32))
    teach_b = jnp.asarray(rng.integers(-50, 50, (B, N), dtype=np.int32))
    seeds = [11, 22, 33]
    rfs = snn_regfile_batch(wts_b, seeds)
    eng = SNNEngine(_plan())
    rfs2, counts, fired = eng.train_batch(rfs, windows, teach_b)
    # stream b == one engine.train on regfile b
    for i in range(B):
        rf_i = snn_regfile(wts_b[i], seed=seeds[i])
        out = eng.train(rf_i, windows[i], teach_b[i])
        np.testing.assert_array_equal(np.asarray(rfs2.weights[i]),
                                      np.asarray(out.regfile.weights))
        np.testing.assert_array_equal(np.asarray(rfs2.lfsr[i]),
                                      np.asarray(out.regfile.lfsr))
        np.testing.assert_array_equal(np.asarray(counts[i]),
                                      np.asarray(out.spike_counts))
        np.testing.assert_array_equal(np.asarray(fired[i]),
                                      np.asarray(out.fired))
    # ... and == the per-cycle scan of every stream
    rfs3, counts3, fired3 = jax.vmap(
        lambda rf, win, tch: _scan_window(_plan(), rf, win, tch))(
            rfs, windows, teach_b)
    _assert_rf_equal(rfs2, rfs3)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(counts3))
    np.testing.assert_array_equal(np.asarray(fired), np.asarray(fired3))


def test_train_batch_rejects_inference_plan():
    weights, windows, _ = _operands()
    rfs = snn_regfile_batch(
        jnp.broadcast_to(weights, (B, N, W)), [1, 2, 3])
    teach = jnp.zeros((B, N), jnp.int32)
    with pytest.raises(ValueError):
        SNNEngine(_plan(w_exp=None)).train_batch(rfs, windows, teach)


def test_stream_helpers_match_legacy_network():
    """engine.train_stream / train_stream_batch == a stream of per-cycle
    scans of ``rvsnn.snn_step`` (v and the spike register reset before
    every sample), stream by stream."""
    weights, windows, _ = _operands(5)
    n_samples = 3
    rng = np.random.default_rng(13)
    trains = jnp.asarray(rng.integers(0, 2**32, (n_samples, T, W),
                                      dtype=np.uint32))
    teach = jnp.asarray(rng.integers(-50, 50, (n_samples, N),
                                     dtype=np.int32))
    eng = SNNEngine(_plan())
    rf = snn_regfile(weights, seed=21)
    got_rf, got_c = train_stream(eng, rf, trains, teach)
    want_rf, want_c = _scan_stream(_plan(), rf, trains, teach)
    _assert_rf_equal(got_rf, want_rf)
    np.testing.assert_array_equal(np.asarray(got_c), np.asarray(want_c))

    wts_b = jnp.broadcast_to(weights, (B, N, W))
    rfs = snn_regfile_batch(wts_b, [5, 6, 7])
    trains_b = jnp.broadcast_to(trains, (B,) + trains.shape)
    teach_b = jnp.broadcast_to(teach, (B,) + teach.shape)
    got_rfs, got_cb = train_stream_batch(eng, rfs, trains_b, teach_b)
    want_rfs, want_cb = jax.vmap(
        lambda r, x, t: _scan_stream(_plan(), r, x, t))(rfs, trains_b,
                                                        teach_b)
    _assert_rf_equal(got_rfs, want_rfs)
    np.testing.assert_array_equal(np.asarray(got_cb),
                                  np.asarray(want_cb))


_ONE_STREAM_CASES = {
    "packed-local": (False, "host", None),
    "packed-mesh": (False, "host", (1, 1)),
    "intensities-host-local": (True, "host", None),
    "intensities-kernel-local": (True, "kernel", None),
    "intensities-host-mesh": (True, "host", (1, 1)),
    "intensities-kernel-mesh": (True, "kernel", (1, 1)),
}


@pytest.mark.parametrize("case", list(_ONE_STREAM_CASES))
def test_train_is_one_stream_of_train_batch(case):
    """``train`` and ``train_stream`` are the B=1 cases of
    ``train_batch`` and ``train_stream_batch``: identical regfiles,
    counts and rasters for both input forms, both encodes and a
    ``mesh_shape=(1, 1)`` plan."""
    inten_form, encode, mesh_shape = _ONE_STREAM_CASES[case]
    weights, windows, teach = _operands(41)
    rng = np.random.default_rng(43)
    inten = jnp.asarray(rng.integers(0, 256, (B, W * 32), dtype=np.uint8))
    eng = SNNEngine(_plan(encode=encode, mesh_shape=mesh_shape,
                          encode_seed=3))
    rf = snn_regfile(weights, seed=45)
    rfs = jax.tree.map(lambda x: x[None], rf)
    if inten_form:
        one = dict(intensities=inten[0], seed=7, n_steps=T)
        many = dict(intensities=inten[:1], seeds=7, n_steps=T)
        stream = dict(intensities=inten, seeds=jnp.arange(B), n_steps=T)
        stream_b = dict(intensities=inten[None], seeds=jnp.arange(B),
                        n_steps=T)
    else:
        one, many = dict(window=windows[0]), dict(windows=windows[:1])
        stream = dict(spike_trains=windows)
        stream_b = dict(spike_trains=windows[None])
    out = eng.train(rf, teach=teach, **one)
    rfs2, counts, fired = eng.train_batch(rfs, teach=teach[None], **many)
    _assert_rf_equal(out.regfile, jax.tree.map(lambda x: x[0], rfs2))
    np.testing.assert_array_equal(np.asarray(out.spike_counts),
                                  np.asarray(counts[0]))
    np.testing.assert_array_equal(np.asarray(out.fired),
                                  np.asarray(fired[0]))

    teach_s = jnp.broadcast_to(teach, (B, N))
    rf_s, counts_s = train_stream(eng, rf, teach=teach_s, **stream)
    rfs_s, counts_b = train_stream_batch(eng, rfs, teach=teach_s[None],
                                         **stream_b)
    _assert_rf_equal(rf_s, jax.tree.map(lambda x: x[0], rfs_s))
    np.testing.assert_array_equal(np.asarray(counts_s),
                                  np.asarray(counts_b[0]))


# --- sharded dispatch (plan placement) ---------------------------------------

def test_sharded_plan_parity_all_verbs():
    """Verbs under a neuron mesh == unsharded verbs == legacy snn_mesh
    entrypoints (whatever mesh this process has)."""
    mesh = snn_mesh.snn_mesh()
    weights, windows, teach = _operands(6)
    plan_m = _plan(mesh=mesh)
    plan_1 = _plan()
    eng_m, eng_1 = SNNEngine(plan_m), SNNEngine(plan_1)

    got = eng_m.infer(weights, windows)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(eng_1.infer(weights, windows)))
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(snn_mesh.sharded_infer_window_batch(
            weights, windows, threshold=KW["threshold"],
            leak=KW["leak"], mesh=mesh)))

    rf = snn_regfile(weights, seed=31)
    out_m = eng_m.train(rf, windows[0], teach)
    out_1 = eng_1.train(rf, windows[0], teach)
    _assert_rf_equal(out_m.regfile, out_1.regfile)
    np.testing.assert_array_equal(np.asarray(out_m.fired),
                                  np.asarray(out_1.fired))

    rng = np.random.default_rng(17)
    wts_b = jnp.asarray(rng.integers(0, 2**32, (B, N, W),
                                     dtype=np.uint32))
    teach_b = jnp.asarray(rng.integers(-50, 50, (B, N), dtype=np.int32))
    rfs = snn_regfile_batch(wts_b, [41, 42, 43])
    lp = jnp.asarray([100, 500, 900], jnp.int32)
    got_m = eng_m.train_batch(rfs, windows, teach_b, ltp_prob=lp)
    got_1 = eng_1.train_batch(rfs, windows, teach_b, ltp_prob=lp)
    _assert_rf_equal(got_m[0], got_1[0])
    np.testing.assert_array_equal(np.asarray(got_m[1]),
                                  np.asarray(got_1[1]))
    np.testing.assert_array_equal(np.asarray(got_m[2]),
                                  np.asarray(got_1[2]))


def test_sharded_train_batch_non_divisible_rows():
    """Stream rows not divisible by the mesh pad + slice transparently."""
    mesh = snn_mesh.snn_mesh()
    d = mesh.shape["neuron"]
    n = d * 2 + 1
    rng = np.random.default_rng(23)
    wts = jnp.asarray(rng.integers(0, 2**32, (2, n, W), dtype=np.uint32))
    spk = jnp.asarray(rng.integers(0, 2**32, (2, T, W), dtype=np.uint32))
    v = jnp.zeros((2, n), jnp.int32)
    teach = jnp.asarray(rng.integers(-50, 50, (2, n), dtype=np.int32))
    st = jnp.stack([lfsr.seed(3 + i, n * W).reshape(n, W)
                    for i in range(2)])
    kw = {k: v2 for k, v2 in KW.items() if k != "ltp_prob"}
    got = snn_mesh.sharded_train_window_batch(
        wts, spk, v, st, teach, ltp_prob=200, mesh=mesh, **kw)
    want = ops.train_window_batch(wts, spk, v, st, teach, ltp_prob=200,
                                  **kw)
    for g, r in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


# --- per-stream ltp_prob (SMEM scalar operand) -------------------------------

@pytest.mark.parametrize("backend,t_chunk", [("ref", None),
                                             ("interp", 4)])
def test_per_stream_ltp_prob_matches_per_plan_runs(backend, t_chunk):
    """train_batch with an i32[B] schedule == per-stream train calls,
    each under a plan pinned to that stream's ltp_prob."""
    weights, windows, _ = _operands(8)
    rng = np.random.default_rng(29)
    wts_b = jnp.asarray(rng.integers(0, 2**32, (B, N, W),
                                     dtype=np.uint32))
    teach_b = jnp.asarray(rng.integers(-50, 50, (B, N), dtype=np.int32))
    seeds = [51, 52, 53]
    rfs = snn_regfile_batch(wts_b, seeds)
    lp = jnp.asarray([16, 500, 1023], jnp.int32)
    eng = SNNEngine(_plan(kernel_backend=backend, t_chunk=t_chunk))
    rfs2, counts, _ = eng.train_batch(rfs, windows, teach_b, ltp_prob=lp)
    for i in range(B):
        plan_i = _plan(kernel_backend=backend, t_chunk=t_chunk,
                       ltp_prob=int(lp[i]))
        out = SNNEngine(plan_i).train(
            snn_regfile(wts_b[i], seed=seeds[i]), windows[i], teach_b[i])
        np.testing.assert_array_equal(np.asarray(rfs2.weights[i]),
                                      np.asarray(out.regfile.weights))
        np.testing.assert_array_equal(np.asarray(rfs2.lfsr[i]),
                                      np.asarray(out.regfile.lfsr))
        np.testing.assert_array_equal(np.asarray(counts[i]),
                                      np.asarray(out.spike_counts))


def test_trainer_parallel_mode_keeps_active_schedule():
    """Parallel training now honors ltp_prob_active for blocks >= 1:
    changing it changes only the later blocks' weights."""
    imgs, labels = make_digits(60, seed=3)
    base = SNNTrainConfig(n_neurons=20, epochs=1, n_steps=16,
                          train_mode="parallel", ltp_prob=16,
                          ltp_prob_active=1023)
    other = dataclasses.replace(base, ltp_prob_active=16)
    m_a = train(base, imgs, labels)
    m_b = train(other, imgs, labels)
    wa, wb = np.asarray(m_a.weights), np.asarray(m_b.weights)
    # block 0 trains at the base ltp_prob in both configs
    np.testing.assert_array_equal(wa[:10], wb[:10])
    # block 1 sees ltp_prob_active 1023 vs 16 -> different weights
    assert (wa[10:] != wb[10:]).any()


# --- 2-D placement: mesh_shape in the plan -----------------------------------

def test_plan_mesh_shape_validation_and_roundtrip():
    cfg = SNNTrainConfig(mesh_shape=(2, 4))
    assert plan_from_config(cfg).mesh_shape == (2, 4)
    # an explicit mesh overrides the config's declarative shape
    m = snn_mesh.snn_mesh()
    p = plan_from_config(cfg, mesh=m)
    assert p.mesh is m and p.mesh_shape is None
    # lists normalize to tuples so the frozen plan stays hashable
    assert _plan(mesh_shape=[1, 1]).mesh_shape == (1, 1)
    for bad in ((0, 2), (2,), (1, 2, 3), ("2", "4")):
        with pytest.raises(ValueError):
            _plan(mesh_shape=bad)
    with pytest.raises(ValueError):
        _plan(mesh_shape=(1, 1), mesh=snn_mesh.snn_mesh())


def test_plan_placement_resolution():
    assert _plan().placement() is None
    m = snn_mesh.snn_mesh()
    assert _plan(mesh=m).placement() is m
    built = _plan(mesh_shape=(1, 1)).placement()
    assert built.shape == {"data": 1, "neuron": 1}


def test_mesh_shape_verbs_match_local_plan():
    """All three verbs through a (1, 1) grid == the unplaced plan,
    bit-exactly (real factorizations run in test_snn_mesh's subprocess
    test; dispatch is identical, only the device grid differs)."""
    weights, windows, teach = _operands(31)
    rng = np.random.default_rng(33)
    local, grid = _plan(), _plan(mesh_shape=(1, 1))

    got = SNNEngine(grid).infer(weights, windows)
    want = SNNEngine(local).infer(weights, windows)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    out_g = SNNEngine(grid).train(snn_regfile(weights, seed=5),
                                  windows[0], teach)
    out_l = SNNEngine(local).train(snn_regfile(weights, seed=5),
                                   windows[0], teach)
    _assert_rf_equal(out_g.regfile, out_l.regfile)
    np.testing.assert_array_equal(np.asarray(out_g.spike_counts),
                                  np.asarray(out_l.spike_counts))

    wts_b = jnp.asarray(rng.integers(0, 2**32, (B, N, W),
                                     dtype=np.uint32))
    teach_b = jnp.asarray(rng.integers(-50, 50, (B, N), dtype=np.int32))
    inten = jnp.asarray(rng.integers(0, 256, (B, W * 32),
                                     dtype=np.uint8))
    seeds = jnp.arange(1, B + 1, dtype=jnp.int32)
    for plan_kw in (dict(), dict(encode="kernel")):
        rfs_g = snn_regfile_batch(wts_b, [7, 8, 9])
        rfs_l = snn_regfile_batch(wts_b, [7, 8, 9])
        eng_g = SNNEngine(_plan(mesh_shape=(1, 1), **plan_kw))
        eng_l = SNNEngine(_plan(**plan_kw))
        kw = (dict(intensities=inten, seeds=seeds, n_steps=T)
              if plan_kw else dict(windows=windows))
        rfs_g2, counts_g, _ = eng_g.train_batch(rfs_g, teach=teach_b,
                                                **kw)
        rfs_l2, counts_l, _ = eng_l.train_batch(rfs_l, teach=teach_b,
                                                **kw)
        _assert_rf_equal(rfs_g2, rfs_l2)
        np.testing.assert_array_equal(np.asarray(counts_g),
                                      np.asarray(counts_l))
