"""Mesh sharding of the SNN window engine: 1-D (neuron) and 2-D
(data × neuron) placements.

These are the low-level shard_map wrappers behind the engine's plan
placement: build an ``SNNEnginePlan(mesh=...)`` (or declaratively,
``mesh_shape=(data, neurons)``) and ``repro.engine.SNNEngine``
dispatches its verbs here — that is the public API.  The functions
remain callable directly (the ``--check``/``--bench`` CLI and older
call sites use them), with unchanged signatures and bit-identical
outputs.

The window kernels grid over neuron blocks independently — every neuron
row owns its weights, membrane and LFSR lanes, and the (small) packed
spike window is shared read-only.  That makes the n axis trivially
spatial: ``shard_map`` the window ops over the "neuron" mesh axis and
each device runs the SAME kernels on its n/D-row shard, with no
collectives and no cross-device PRNG state.  Populations then scale
past one core's VMEM by adding devices.

The batched ops add a second independent axis: streams/samples.  Each
stream owns its regfile (batched training) or its window/intensity row
(batched serving), and the encode-fused kernels draw spikes from
per-sample *counter-hash* seeds — stateless, so any device regenerates
any (seed, cycle, input) bit identically.  ``snn_mesh2d(data,
neurons)`` therefore factorizes the device grid over BOTH axes::

                 neuron axis (populations) ->
               +----------------+----------------+
      data     |  dev(0,0)      |  dev(0,1)      |   samples 0..B/2
      axis     |  rows 0..n/2   |  rows n/2..n   |
    (samples)  +----------------+----------------+
        |      |  dev(1,0)      |  dev(1,1)      |   samples B/2..B
        v      |  rows 0..n/2   |  rows n/2..n   |
               +----------------+----------------+

Device (i, j) trains/serves its sample rows × its neuron rows; no
collectives, no cross-shard PRNG state, and any (data, neurons)
factorization — (2,4), (4,2), (8,1), … — is bit-exact with the 1-D and
unsharded paths.  The same wrappers serve every placement: batch axes
carry the "data" logical name, which resolves to the "data" mesh axis
when present and to replicated on a 1-D neuron mesh.

Specs come from the logical-axis machinery in
:mod:`repro.distributed.sharding`: state matrices are ("neurons",
"syn_words") — with a leading "data" axis when batched — per-neuron
vectors ("neurons",), per-sample scalars ("data",), spike windows and
intensities ("data", …) with the word axis replicated.

Entry point (runs on a forced-multi-device CPU mesh in containers
without TPUs)::

    python -m repro.distributed.snn_mesh --check            # 8 devices
    python -m repro.distributed.snn_mesh --check \
        --mesh-shape 2,4 --mesh-shape 4,2 --mesh-shape 8,1  # 2-D grids
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python -m repro.distributed.snn_mesh --check --devices 4

``--check`` asserts sharded == single-device outputs bit-exactly for
every wrapper (pre-packed and encode-fused, infer and train) on each
requested mesh.
"""

from __future__ import annotations

import functools
import os
import sys

if __name__ == "__main__":  # before any jax backend initialization
    _flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8").strip()

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.distributed.sharding import logical_spec, use_rules
from repro.kernels import ops

_AXIS = "neuron"
_DATA_AXIS = "data"


def snn_mesh(n_devices: int | None = None) -> Mesh:
    """1-D neuron mesh over (the first n of) the available devices."""
    devs = jax.devices()
    if n_devices is not None:
        if n_devices > len(devs):
            raise ValueError(f"asked for {n_devices} devices, "
                             f"have {len(devs)}")
        devs = devs[:n_devices]
    import numpy as np
    return Mesh(np.asarray(devs), (_AXIS,))


def snn_mesh2d(data: int, neurons: int) -> Mesh:
    """2-D (data × neuron) mesh over the first data*neurons devices.

    Sample/stream batch axes shard over ``data``, neuron rows over
    ``neurons``; ``snn_mesh2d(1, d)`` and ``snn_mesh(d)`` produce
    bit-identical results through every wrapper below.
    """
    if data < 1 or neurons < 1:
        raise ValueError(f"mesh extents must be >= 1, got "
                         f"({data}, {neurons})")
    devs = jax.devices()
    need = data * neurons
    if need > len(devs):
        raise ValueError(f"asked for a {data}x{neurons} mesh "
                         f"({need} devices), have {len(devs)}")
    import numpy as np
    return Mesh(np.asarray(devs[:need]).reshape(data, neurons),
                (_DATA_AXIS, _AXIS))


def _dims(mesh: Mesh) -> tuple[int, int]:
    """(data, neuron) extents; data is 1 on a 1-D neuron mesh."""
    return mesh.shape.get(_DATA_AXIS, 1), mesh.shape[_AXIS]


def _specs(mesh: Mesh, *names_tuples):
    rules = use_rules()
    return tuple(logical_spec(names, rules, mesh) for names in names_tuples)


def _pad_rows(x: jnp.ndarray, mult: int, fill=0, axis: int = 0
              ) -> jnp.ndarray:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


def sharded_infer_window_batch(weights, spike_trains, *, threshold: int,
                               leak: int, t_chunk: int | None = None,
                               backend: str = "ref",
                               mesh: Mesh | None = None) -> jnp.ndarray:
    """:func:`ops.infer_window_batch` over an SNN mesh.

    weights u32[n, w] shard on n; spike_trains u32[B, T, w] shard on B
    over the "data" axis (replicated on a 1-D neuron mesh); counts
    i32[B, n] come back sharded on both and are reassembled.  Bit-exact
    with the single-device op for any mesh factorization.
    """
    mesh = snn_mesh() if mesh is None else mesh
    dd, nd = _dims(mesh)
    n = weights.shape[0]
    b = spike_trains.shape[0]
    wp = _pad_rows(weights, nd)
    tp = _pad_rows(spike_trains, dd)
    row, trains, out = _specs(mesh, ("neurons", "syn_words"),
                              ("data", None, "syn_words"),
                              ("data", "neurons"))
    fn = jax.shard_map(
        functools.partial(ops.infer_window_batch, threshold=threshold,
                          leak=leak, t_chunk=t_chunk, backend=backend),
        mesh=mesh, in_specs=(row, trains), out_specs=out, check_vma=False)
    return fn(wp, tp)[:b, :n]


def _refuse_coupling(w_inh: int, theta_plus: int, theta) -> None:
    """The sharded train ops split a population's neurons over devices,
    which a population coupled within a cycle cannot be."""
    if w_inh:
        raise ValueError(
            "lateral inhibition (w_inh > 0) couples every neuron of a "
            "population each cycle, so a neuron axis split over devices "
            "would need a collective every cycle; one chip holds the "
            "population: train it unsharded")
    if theta_plus or theta is not None:
        raise ValueError("adaptive thresholds (theta) are not carried by "
                         "the sharded train ops: train unsharded")


def sharded_train_window_batch(weights, spike_trains, v, lfsr_state,
                               teach, *, threshold: int, leak: int,
                               w_exp: int, gain: int, n_syn: int,
                               ltp_prob=1023, t_chunk: int | None = None,
                               backend: str = "ref",
                               mesh: Mesh | None = None, theta=None,
                               w_inh: int = 0, theta_plus: int = 0):
    """:func:`ops.train_window_batch` over an SNN mesh.

    weights/lfsr u32[B, n, w], v/teach i32[B, n] shard on n AND on the
    stream axis over "data" (every stream's rows travel with their LFSR
    lanes); the spike windows u32[B, T, w] and the per-stream
    ``ltp_prob`` (int or i32[B]) shard on "data" only.  On a 2-D
    (data × neuron) mesh device (i, j) trains its B/dd streams × its
    n/nd rows; bit-exact with the single-device op for any
    factorization.  Returns (weights', v', fired bool[B, T, n], lfsr').
    Refuses lateral inhibition and adaptive thresholds (``w_inh``,
    ``theta_plus``, ``theta``).
    """
    _refuse_coupling(w_inh, theta_plus, theta)
    mesh = snn_mesh() if mesh is None else mesh
    dd, nd = _dims(mesh)
    b, n, _ = weights.shape
    wp = _pad_rows(_pad_rows(weights, nd, axis=1), dd)
    vp = _pad_rows(_pad_rows(v, nd, axis=1), dd)
    tp = _pad_rows(_pad_rows(teach, nd, axis=1), dd)
    sp = _pad_rows(_pad_rows(lfsr_state, nd, fill=1, axis=1), dd, fill=1)
    kp = _pad_rows(spike_trains, dd)
    lp = _pad_rows(
        jnp.broadcast_to(jnp.asarray(ltp_prob, jnp.int32), (b,)), dd)
    row3, vecb, trains, per, ras3 = _specs(
        mesh, ("data", "neurons", "syn_words"), ("data", "neurons"),
        ("data", None, "syn_words"), ("data",), ("data", None, "neurons"))

    def call(w, s, vv, st, tc, lp_):
        return ops.train_window_batch(
            w, s, vv, st, tc, threshold=threshold, leak=leak,
            w_exp=w_exp, gain=gain, n_syn=n_syn, ltp_prob=lp_,
            t_chunk=t_chunk, backend=backend)

    fn = jax.shard_map(call, mesh=mesh,
                       in_specs=(row3, trains, vecb, row3, vecb, per),
                       out_specs=(row3, vecb, ras3, row3), check_vma=False)
    w2, v2, fired, s2 = fn(wp, kp, vp, sp, tp, lp)
    return w2[:b, :n], v2[:b, :n], fired[:b, :, :n], s2[:b, :n]


def sharded_infer_window_batch_encode(weights, intensities, seeds, *,
                                      n_steps: int, threshold: int,
                                      leak: int, t_total=None,
                                      t_chunk: int | None = None,
                                      backend: str = "ref",
                                      mesh: Mesh | None = None
                                      ) -> jnp.ndarray:
    """:func:`ops.infer_window_batch_encode` over an SNN mesh.

    weights shard on n; intensities u8[B, n_in], per-sample seeds and
    the optional ``t_total`` shard on "data" — the counter draw is
    stateless, so every neuron shard regenerates the SAME spikes from
    its sample rows' (seed, cycle) keys with no cross-shard broadcast.
    Bit-exact with the single-device op for any factorization.
    """
    mesh = snn_mesh() if mesh is None else mesh
    dd, nd = _dims(mesh)
    n = weights.shape[0]
    b = intensities.shape[0]
    wp = _pad_rows(weights, nd)
    xp = _pad_rows(intensities, dd)
    sd = _pad_rows(
        jnp.broadcast_to(jnp.asarray(seeds, jnp.int32), (b,)), dd)
    tt = (jnp.full((b,), n_steps, jnp.int32) if t_total is None
          else jnp.asarray(t_total, jnp.int32))
    tt = _pad_rows(tt, dd, fill=n_steps)
    row, inten, per, out = _specs(mesh, ("neurons", "syn_words"),
                                  ("data", None), ("data",),
                                  ("data", "neurons"))

    def call(w, x, s, t):
        return ops.infer_window_batch_encode(
            w, x, s, n_steps=n_steps, threshold=threshold, leak=leak,
            t_total=t, t_chunk=t_chunk, backend=backend)

    fn = jax.shard_map(call, mesh=mesh, in_specs=(row, inten, per, per),
                       out_specs=out, check_vma=False)
    return fn(wp, xp, sd, tt)[:b, :n]


def sharded_train_window_batch_encode(weights, intensities, seeds, v,
                                      lfsr_state, teach, *, n_steps: int,
                                      threshold: int, leak: int,
                                      w_exp: int, gain: int, n_syn: int,
                                      ltp_prob=1023,
                                      t_chunk: int | None = None,
                                      backend: str = "ref",
                                      mesh: Mesh | None = None,
                                      theta=None, w_inh: int = 0,
                                      theta_plus: int = 0):
    """:func:`ops.train_window_batch_encode` over an SNN mesh.

    Per-stream state shards on n and on "data"; intensities u8[B, n_in],
    per-sample seeds and ``ltp_prob`` shard on "data" only — each
    stream's n_in intensity bytes land exactly on the devices training
    that stream, so the 2-D mesh is the end-to-end intensity-resident
    placement: no spike window in HBM anywhere, no replicated dataset.
    Bit-exact with the single-device op for any factorization.  Refuses
    lateral inhibition and adaptive thresholds, as
    :func:`sharded_train_window_batch` does.
    """
    _refuse_coupling(w_inh, theta_plus, theta)
    mesh = snn_mesh() if mesh is None else mesh
    dd, nd = _dims(mesh)
    b, n, _ = weights.shape
    wp = _pad_rows(_pad_rows(weights, nd, axis=1), dd)
    vp = _pad_rows(_pad_rows(v, nd, axis=1), dd)
    tp = _pad_rows(_pad_rows(teach, nd, axis=1), dd)
    sp = _pad_rows(_pad_rows(lfsr_state, nd, fill=1, axis=1), dd, fill=1)
    xp = _pad_rows(intensities, dd)
    lp = _pad_rows(
        jnp.broadcast_to(jnp.asarray(ltp_prob, jnp.int32), (b,)), dd)
    sd = _pad_rows(
        jnp.broadcast_to(jnp.asarray(seeds, jnp.int32), (b,)), dd)
    row3, vecb, inten, per, ras3 = _specs(
        mesh, ("data", "neurons", "syn_words"), ("data", "neurons"),
        ("data", None), ("data",), ("data", None, "neurons"))

    def call(w, x, s, vv, st, tc, lp_):
        return ops.train_window_batch_encode(
            w, x, s, vv, st, tc, n_steps=n_steps, threshold=threshold,
            leak=leak, w_exp=w_exp, gain=gain, n_syn=n_syn, ltp_prob=lp_,
            t_chunk=t_chunk, backend=backend)

    fn = jax.shard_map(call, mesh=mesh,
                       in_specs=(row3, inten, per, vecb, row3, vecb, per),
                       out_specs=(row3, vecb, ras3, row3), check_vma=False)
    w2, v2, fired, s2 = fn(wp, xp, sd, vp, sp, tp, lp)
    return w2[:b, :n], v2[:b, :n], fired[:b, :, :n], s2[:b, :n]


def _parse_mesh_shapes(shapes) -> list[tuple[int, int]]:
    out = []
    for s in shapes or []:
        parts = s.split(",")
        if len(parts) != 2:
            raise SystemExit(f"--mesh-shape wants D,N — got {s!r}")
        out.append((int(parts[0]), int(parts[1])))
    return out


def _meshes(args) -> list[Mesh]:
    shapes = _parse_mesh_shapes(args.mesh_shape)
    if shapes:
        return [snn_mesh2d(d, n) for d, n in shapes]
    return [snn_mesh(args.devices)]


def _mesh_label(mesh: Mesh) -> str:
    dd, nd = _dims(mesh)
    if _DATA_AXIS in mesh.shape:
        return f"{dd}x{nd} mesh"
    return f"{nd} devices"


def _check(args) -> int:
    import numpy as np

    rng = np.random.default_rng(0x22A)
    n, w, t, b = args.neurons, args.words, args.steps, args.batch
    weights = jnp.asarray(rng.integers(0, 2**32, (n, w), dtype=np.uint32))
    trains = jnp.asarray(
        rng.integers(0, 2**32, (b, t, w), dtype=np.uint32))
    from repro.core import lfsr
    kw = dict(threshold=60, leak=4, w_exp=64, gain=4, n_syn=w * 32,
              ltp_prob=200)
    inten = jnp.asarray(rng.integers(0, 256, (b, w * 32), dtype=np.uint8))
    seeds = jnp.arange(1, b + 1, dtype=jnp.int32)
    tt = jnp.asarray([t - (i % 3) for i in range(b)], jnp.int32)
    wts_b = jnp.asarray(
        rng.integers(0, 2**32, (b, n, w), dtype=np.uint32))
    vb = jnp.zeros((b, n), jnp.int32)
    tb = jnp.asarray(rng.integers(-50, 50, (b, n), dtype=np.int32))
    stb = jnp.stack([lfsr.seed(3 + i, n * w).reshape(n, w)
                     for i in range(b)])

    for mesh in _meshes(args):
        label = _mesh_label(mesh)

        got = sharded_infer_window_batch(
            weights, trains, threshold=60, leak=4, backend=args.backend,
            mesh=mesh)
        want = ops.infer_window_batch(weights, trains, threshold=60,
                                      leak=4, backend=args.backend)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        print(f"infer_window_batch: sharded({label}) == single-device "
              f"[B={b}, n={n}]")

        got = sharded_train_window_batch(
            wts_b, trains, vb, stb, tb, backend=args.backend, mesh=mesh,
            **kw)
        want = ops.train_window_batch(wts_b, trains, vb, stb, tb,
                                      backend=args.backend, **kw)
        for g, r in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(r))
        print(f"train_window_batch: sharded({label}) == single-device "
              f"[B={b}]")

        # encode-fused paths: every shard regenerates the same spikes
        # from its samples' seeds (stateless counter draw)
        got = sharded_infer_window_batch_encode(
            weights, inten, seeds, n_steps=t, threshold=60, leak=4,
            t_total=tt, backend=args.backend, mesh=mesh)
        want = ops.infer_window_batch_encode(
            weights, inten, seeds, n_steps=t, threshold=60, leak=4,
            t_total=tt, backend=args.backend)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        print(f"infer_window_batch_encode: sharded({label}) == "
              f"single-device [B={b}, ragged T]")

        got = sharded_train_window_batch_encode(
            wts_b, inten, seeds, vb, stb, tb, n_steps=t,
            backend=args.backend, mesh=mesh, **kw)
        want = ops.train_window_batch_encode(
            wts_b, inten, seeds, vb, stb, tb, n_steps=t,
            backend=args.backend, **kw)
        for g, r in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(r))
        print(f"train_window_batch_encode: sharded({label}) == "
              f"single-device [B={b}]")
    print("OK")
    return 0


def _bench(args) -> int:
    """Time sharded vs single-device serving; print one parseable line.

    Meant to run in a fresh process (benchmarks/kernels_bench.py spawns
    it with --xla_force_host_platform_device_count) so the forced
    multi-device CPU mesh cannot skew the parent's timings.  With
    ``--mesh-shape D,N`` it instead times the batched TRAINING grid on
    the 2-D mesh vs the 1-D neuron mesh of the same device count
    (``BENCH2D`` line).
    """
    import time as _time

    import numpy as np

    rng = np.random.default_rng(5)
    n, w, t, b = args.neurons, args.words, args.steps, args.batch

    def med_us(fn, *operands):
        for _ in range(2):
            jax.block_until_ready(fn(*operands))
        ts = []
        for _ in range(5):
            t0 = _time.perf_counter()
            jax.block_until_ready(fn(*operands))
            ts.append(_time.perf_counter() - t0)
        return float(np.median(ts) * 1e6)

    shapes = _parse_mesh_shapes(args.mesh_shape)
    if shapes:
        from repro.core import lfsr
        wts = jnp.asarray(
            rng.integers(0, 2**32, (b, n, w), dtype=np.uint32))
        spk = jnp.asarray(
            rng.integers(0, 2**32, (b, t, w), dtype=np.uint32))
        vb = jnp.zeros((b, n), jnp.int32)
        tb = jnp.zeros((b, n), jnp.int32)
        stb = jnp.stack([lfsr.seed(1 + i, n * w).reshape(n, w)
                         for i in range(b)])
        kw = dict(threshold=192, leak=16, w_exp=128, gain=4,
                  n_syn=w * 32, ltp_prob=16, backend=args.backend)
        for dd, nd in shapes:
            f1 = jax.jit(functools.partial(sharded_train_window_batch,
                                           mesh=snn_mesh(dd * nd), **kw))
            f2 = jax.jit(functools.partial(sharded_train_window_batch,
                                           mesh=snn_mesh2d(dd, nd),
                                           **kw))
            t_1, t_2 = (med_us(f, wts, spk, vb, stb, tb)
                        for f in (f1, f2))
            print(f"BENCH2D shape={dd}x{nd} b={b} n={n} words={w} "
                  f"t_1d_us={t_1:.2f} t_2d_us={t_2:.2f}")
        return 0

    mesh = snn_mesh(args.devices)
    d = mesh.shape[_AXIS]
    weights = jnp.asarray(rng.integers(0, 2**32, (n, w), dtype=np.uint32))
    trains = jnp.asarray(
        rng.integers(0, 2**32, (b, t, w), dtype=np.uint32))
    single = jax.jit(functools.partial(
        ops.infer_window_batch, threshold=192, leak=16,
        backend=args.backend))
    # jit once so repeated calls hit the compile cache — timing a fresh
    # shard_map build per call would measure tracing, not execution
    shard = jax.jit(functools.partial(
        sharded_infer_window_batch, threshold=192, leak=16,
        backend=args.backend, mesh=mesh))

    t_1, t_d = med_us(single, weights, trains), med_us(shard, weights,
                                                       trains)
    print(f"BENCH devices={d} n={n} words={w} t_single_us={t_1:.2f} "
          f"t_shard_us={t_d:.2f}")
    return 0


def main(argv: list[str] | None = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--devices", type=int, default=None,
                    help="1-D mesh size (default: all devices)")
    ap.add_argument("--mesh-shape", action="append", default=None,
                    metavar="D,N",
                    help="2-D (data × neuron) factorization; repeatable "
                         "— each D,N grid is checked in turn")
    ap.add_argument("--neurons", type=int, default=264)
    ap.add_argument("--words", type=int, default=25)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--backend", default="ref",
                    choices=["ref", "interp", "tpu"])
    ap.add_argument("--check", action="store_true",
                    help="assert sharded == unsharded and exit")
    ap.add_argument("--bench", action="store_true",
                    help="time sharded vs single-device and exit")
    args = ap.parse_args(argv)
    print(f"devices: {jax.device_count()} "
          f"({jax.devices()[0].platform})")
    if args.bench:
        return _bench(args)
    return _check(args)


if __name__ == "__main__":
    sys.exit(main())
