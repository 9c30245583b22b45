"""Neuron-axis mesh sharding of the window engine.

The multi-device case needs ``--xla_force_host_platform_device_count``
set before jax initializes, so it runs in a subprocess; the in-process
test exercises the same shard_map path on whatever mesh this process
has (1 CPU device under plain pytest).
"""

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lfsr
from repro.distributed import snn_mesh
from repro.kernels import ops

REPO = Path(__file__).resolve().parents[1]


def test_sharded_ops_match_unsharded_on_local_mesh():
    mesh = snn_mesh.snn_mesh()
    rng = np.random.default_rng(4)
    n, w, t, b = 24, 5, 9, 3
    weights = jnp.asarray(rng.integers(0, 2**32, (n, w), dtype=np.uint32))
    trains = jnp.asarray(
        rng.integers(0, 2**32, (b, t, w), dtype=np.uint32))
    v = jnp.zeros((n,), jnp.int32)
    teach = jnp.asarray(rng.integers(-50, 50, (n,), dtype=np.int32))
    st = lfsr.seed(7, n * w).reshape(n, w)
    kw = dict(threshold=60, leak=4, w_exp=64, gain=4, n_syn=w * 32,
              ltp_prob=200)

    got = snn_mesh.sharded_infer_window_batch(
        weights, trains, threshold=60, leak=4, mesh=mesh)
    want = ops.infer_window_batch(weights, trains, threshold=60, leak=4)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    # one stream: the B=1 launch of the batched train op
    one = (weights[None], trains[:1], v[None], st[None], teach[None])
    got = snn_mesh.sharded_train_window_batch(*one, mesh=mesh, **kw)
    want = ops.train_window_batch(*one, **kw)
    for g, r in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


def test_sharded_handles_non_divisible_neuron_axis():
    """n not a multiple of the mesh size pads + slices transparently."""
    mesh = snn_mesh.snn_mesh()
    d = mesh.shape["neuron"]
    n, w = d * 4 + 3, 3
    rng = np.random.default_rng(8)
    weights = jnp.asarray(rng.integers(0, 2**32, (n, w), dtype=np.uint32))
    trains = jnp.asarray(rng.integers(0, 2**32, (2, 6, w),
                                      dtype=np.uint32))
    got = snn_mesh.sharded_infer_window_batch(
        weights, trains, threshold=20, leak=2, mesh=mesh)
    want = ops.infer_window_batch(weights, trains, threshold=20, leak=2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_sharded_2d_ops_match_unsharded_on_local_mesh():
    """The 2-D wrappers run the (1, 1) degenerate grid in-process:
    batch axes spec'd over "data", state over "neurons", bit-exact with
    the unsharded ops (the real factorizations run in the subprocess
    test below)."""
    mesh = snn_mesh.snn_mesh2d(1, 1)
    rng = np.random.default_rng(11)
    n, w, t, b = 24, 5, 9, 3
    kw = dict(threshold=60, leak=4, w_exp=64, gain=4, n_syn=w * 32,
              ltp_prob=200)
    trains = jnp.asarray(
        rng.integers(0, 2**32, (b, t, w), dtype=np.uint32))
    wts_b = jnp.asarray(
        rng.integers(0, 2**32, (b, n, w), dtype=np.uint32))
    vb = jnp.zeros((b, n), jnp.int32)
    tb = jnp.asarray(rng.integers(-50, 50, (b, n), dtype=np.int32))
    stb = jnp.stack([lfsr.seed(3 + i, n * w).reshape(n, w)
                     for i in range(b)])
    inten = jnp.asarray(rng.integers(0, 256, (b, w * 32),
                                     dtype=np.uint8))
    seeds = jnp.arange(1, b + 1, dtype=jnp.int32)

    got = snn_mesh.sharded_train_window_batch(
        wts_b, trains, vb, stb, tb, mesh=mesh, **kw)
    want = ops.train_window_batch(wts_b, trains, vb, stb, tb, **kw)
    for g, r in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))

    got = snn_mesh.sharded_train_window_batch_encode(
        wts_b, inten, seeds, vb, stb, tb, n_steps=t, mesh=mesh, **kw)
    want = ops.train_window_batch_encode(
        wts_b, inten, seeds, vb, stb, tb, n_steps=t, **kw)
    for g, r in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))

    got = snn_mesh.sharded_infer_window_batch_encode(
        wts_b[0], inten, seeds, n_steps=t, threshold=60, leak=4,
        mesh=mesh)
    want = ops.infer_window_batch_encode(
        wts_b[0], inten, seeds, n_steps=t, threshold=60, leak=4)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.slow
def test_multi_device_host_mesh_subprocess():
    """Sharded == unsharded on a real 8-device CPU mesh (fresh jax)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8"
                        ).strip()
    env["PYTHONPATH"] = (str(REPO / "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.distributed.snn_mesh", "--check",
         "--devices", "8", "--neurons", "64", "--words", "5",
         "--steps", "8", "--batch", "2"],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "sharded(8 devices) == single-device" in proc.stdout


@pytest.mark.slow
def test_2d_factorizations_subprocess():
    """(2,4), (4,2) and (8,1) grids of the same 8 host devices are all
    bit-exact with the unsharded oracle — pre-packed AND encode-fused,
    infer AND train_batch — in one fresh-jax subprocess (batch 5 and 26
    neurons don't divide any factorization, so padding is exercised
    everywhere)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8"
                        ).strip()
    env["PYTHONPATH"] = (str(REPO / "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.distributed.snn_mesh", "--check",
         "--mesh-shape", "2,4", "--mesh-shape", "4,2",
         "--mesh-shape", "8,1", "--neurons", "26", "--words", "5",
         "--steps", "8", "--batch", "5"],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for lbl in ("2x4", "4x2", "8x1"):
        for op in ("infer_window_batch", "train_window_batch",
                   "infer_window_batch_encode",
                   "train_window_batch_encode"):
            assert (f"{op}: sharded({lbl} mesh) == single-device"
                    in proc.stdout), (lbl, op, proc.stdout)
