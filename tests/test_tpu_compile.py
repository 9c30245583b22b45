"""Compile rehearsals of the window kernels for a described TPU v5e.

Interpret mode checks what a kernel computes, not whether the TPU
compiler accepts its blocks: block tiling, SMEM operands and Mosaic's
vector shape casts are only checked by compiling for the chip.  These
tests compile the main path's window ops at the paper's width (784
inputs, 40 neurons, T=72, B=32, and the one-stream B=1 launches that
``SNNEngine.train`` makes), the packed serving op at the
4,096-neuron ensemble, the encode serving op at the offline cell's
shape (6,400 neurons, B=256), the encode train op at the unsupervised
cell's shape (one stream of 6,400 neurons with lateral inhibition and
adaptive thresholds, T=350), and one sharded infer on a described 2x2
mesh, each for a v5e that is described, not attached (nothing runs).

The topology is described inside module fixtures (never at import):
only one process at a time may load the TPU library, and with several
test workers only the one given this file should.
"""

from __future__ import annotations

import functools
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from repro.distributed import snn_mesh
from repro.kernels import ops

N_IN_WORDS, T, B = 25, 72, 32          # 784 inputs -> 25 packed words
LIF = dict(threshold=192, leak=16)
SU = dict(w_exp=128, gain=4, n_syn=784)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described-chip compile cannot be read back from the persistent
    cache without a chip; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_persistent_cache):
    return SingleDeviceSharding(topo.devices[0])


def _window_op_cases(sharding, n: int, b: int = B):
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    u, i, u8 = jnp.uint32, jnp.int32, jnp.uint8
    w = N_IN_WORDS
    return {
        "infer_window_batch": (
            ops.infer_window_batch, (s((n, w), u), s((B, T, w), u)),
            dict(LIF)),
        "infer_window_batch_encode": (
            ops.infer_window_batch_encode,
            (s((n, w), u), s((b, 784), u8), s((b,), i)),
            dict(n_steps=T, t_total=s((b,), i), **LIF)),
        "train_window_batch": (
            ops.train_window_batch,
            (s((B, n, w), u), s((B, T, w), u), s((B, n), i),
             s((B, n, w), u), s((B, n), i)),
            dict(ltp_prob=s((B,), i), **LIF, **SU)),
        "train_window_batch_encode": (
            ops.train_window_batch_encode,
            (s((B, n, w), u), s((B, 784), u8), s((B,), i), s((B, n), i),
             s((B, n, w), u), s((B, n), i)),
            dict(n_steps=T, ltp_prob=s((B,), i), **LIF, **SU)),
        # one stream (SNNEngine.train): the B=1 launches
        "train_window_batch_b1": (
            ops.train_window_batch,
            (s((1, n, w), u), s((1, T, w), u), s((1, n), i),
             s((1, n, w), u), s((1, n), i)),
            dict(ltp_prob=16, **LIF, **SU)),
        "train_window_batch_encode_b1": (
            ops.train_window_batch_encode,
            (s((1, n, w), u), s((1, 784), u8), s((1,), i), s((1, n), i),
             s((1, n, w), u), s((1, n), i)),
            dict(n_steps=T, ltp_prob=16, **LIF, **SU)),
    }


def _compiled_text(op, args, kw) -> str:
    return op.lower(*args, backend="tpu", **kw).compile().as_text()


@pytest.mark.parametrize("name", [
    "infer_window_batch", "infer_window_batch_encode",
    "train_window_batch", "train_window_batch_encode",
    "train_window_batch_b1", "train_window_batch_encode_b1"])
def test_window_op_compiles_for_v5e_at_paper_width(one_chip, name):
    op, args, kw = _window_op_cases(one_chip, 40)[name]
    assert "tpu_custom_call" in _compiled_text(op, args, kw)


def test_infer_window_batch_compiles_for_v5e_at_4096_neurons(one_chip):
    op, args, kw = _window_op_cases(one_chip, 4096)["infer_window_batch"]
    assert "tpu_custom_call" in _compiled_text(op, args, kw)


def test_infer_encode_compiles_for_v5e_at_offline_shape(one_chip):
    """The offline cell's launch (6,400 neurons, 256 windows, T=72): the
    MXU kernel's tiles and scratch fit, and its operation keeps the name
    the device trace finds it by."""
    op, args, kw = _window_op_cases(one_chip, 6400, 256)[
        "infer_window_batch_encode"]
    text = _compiled_text(op, args, kw)
    assert "tpu_custom_call" in text
    assert re.search(r"%infer_window_batch_encode(\.\d+)? = \S+ "
                     r"custom-call\(", text)


def test_train_encode_compiles_for_v5e_at_the_wta_cell_shape(one_chip):
    """The unsupervised cell's launch (one stream, 6,400 neurons coupled
    by lateral inhibition, adaptive thresholds, T=350): the whole
    population fits one block in VMEM, and the operation keeps the name
    the device trace finds it by."""
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    u, i, n = jnp.uint32, jnp.int32, 6400
    args = (s((1, n, N_IN_WORDS), u), s((1, 784), jnp.uint8), s((1,), i),
            s((1, n), i), s((1, n, N_IN_WORDS), u), s((1, n), i))
    kw = dict(n_steps=350, threshold=40, leak=0, w_exp=78, gain=4,
              n_syn=784, ltp_prob=s((1,), i), theta=s((1, n), i),
              w_inh=40, theta_plus=1)
    text = _compiled_text(ops.train_window_batch_encode, args, kw)
    assert "tpu_custom_call" in text
    assert re.search(r"%train_window_batch_encode(\.\d+)? = \(", text)


def test_sharded_infer_compiles_on_described_2x2_mesh(topo,
                                                      no_persistent_cache):
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "neuron"))
    rep = NamedSharding(mesh, PartitionSpec())
    weights = jax.ShapeDtypeStruct((4096, N_IN_WORDS), jnp.uint32,
                                   sharding=rep)
    windows = jax.ShapeDtypeStruct((256, T, N_IN_WORDS), jnp.uint32,
                                   sharding=rep)
    fn = jax.jit(functools.partial(snn_mesh.sharded_infer_window_batch,
                                   backend="tpu", mesh=mesh, **LIF))
    compiled = fn.lower(weights, windows).compile()
    assert "tpu_custom_call" in compiled.as_text()
    out = compiled.output_shardings
    assert len(out.device_set) == 4
