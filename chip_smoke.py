"""Chip smoke: the Wenquxing 22A serve and train path on a TPU, through
its Pallas kernels, at the paper's full width (784 inputs, 40 neurons,
T=72, 1-bit synapses, binary stochastic STDP).

    python chip_smoke.py               # one chip: phases (a)-(d)
    python chip_smoke.py --four-chips  # the data x neuron mesh on a 2x2 host

Phases of the one-chip run:

(a) fail unless ``jax.devices()[0].platform == "tpu"`` (no CPU fallback);
(b) each window op compiled with ``backend="tpu"`` (the compiled text
    must hold ``tpu_custom_call``) and equal to ``backend="ref"`` on the
    same chip;
(c) ``SNNServingEngine`` serves 256 procedural-digit requests on the
    wall clock (intensity and pre-packed, ragged T <= 72, max_batch=32,
    ``encode="kernel"``, the platform's default kernel backend); every
    request must end SERVED with the reference counts of its weight
    version, with no degraded launch and no integrity or canary failure;
(d) ``trainer.train`` (``WENQUXING_22A_INTENSITY``, parallel mode, 64
    samples, one epoch) on ``"tpu"`` must give the ``"ref"`` weights bit
    for bit.

``--four-chips`` runs only the sharded path and what it is compared
with: the ``snn_mesh`` infer and train-batch ops (pre-packed and
encode-fused) at 4,096 neurons x 784 inputs, B=256, T=72, on meshes
(1, 4) and (2, 2), each equal to the one-chip result and spread over
four devices, plus a few requests served through a ``mesh_shape=(2, 2)``
plan.

Each phase prints lines starting ``smoke`` with the backend, shapes,
compile seconds and wall seconds: smoke timings, not a benchmark.  The
last line of stdout is ``{"ok": true, "device": {...}}``, printed only
when every phase passed; otherwise the script exits 1 (2 without a TPU).
Weights and data are random or procedural, made from ``--seed``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# the paper's width (WENQUXING_22A) and the serving/training batch
N_IN, N_NEURONS, T, B = 784, 40, 72, 32
LIF = dict(threshold=192, leak=16)
SU = dict(w_exp=128, gain=4, n_syn=N_IN)
MESHES = ((1, 4), (2, 2))   # (data, neurons) factorizations of 4 chips


def _line(phase: str, **fields) -> None:
    print(f"smoke ({phase}) " + " ".join(f"{k}={v}"
                                         for k, v in fields.items()),
          flush=True)


def _shapes(*xs) -> str:
    return ",".join("x".join(map(str, x.shape)) for x in xs)


def _equal(a, b) -> bool:
    import jax
    import numpy as np

    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


def _digits(n: int, seed: int):
    """uint8 intensities [n, 784] and labels of procedural digits."""
    import numpy as np

    from repro.core.encoder import quantize_intensities
    from repro.data.digits import make_digits

    imgs, labels = make_digits(n, seed=seed)
    return np.asarray(quantize_intensities(imgs)), labels


def _windows(inten, seeds, n_steps: int, words: int):
    """Host counter-encoded packed windows uint32[B, T, words]."""
    import jax.numpy as jnp

    from repro.core.encoder import encode_windows_host

    return encode_windows_host(jnp.asarray(seeds, jnp.int32),
                               jnp.asarray(inten), n_steps, words)


def _operands(n: int, b: int, seed: int):
    """Window-op operands at width (n neurons, 784 inputs, T, b)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import lfsr
    from repro.core.bitpack import n_words

    words = n_words(N_IN)
    rng = np.random.default_rng(seed)
    inten, labels = _digits(b, seed)
    seeds = np.arange(b, dtype=np.int32) + seed
    onehot = np.arange(n)[None, :] % 10 == labels[:, None]
    teach = jnp.asarray(np.where(onehot, 64, -1024), jnp.int32)
    return dict(
        weights=jnp.asarray(rng.integers(0, 2**32, (n, words), np.uint32)),
        wts_b=jnp.asarray(rng.integers(0, 2**32, (b, n, words), np.uint32)),
        lfsr_b=lfsr.seed(seed, b * n * words).reshape(b, n, words),
        v_b=jnp.zeros((b, n), jnp.int32),
        teach_b=teach,
        inten=jnp.asarray(inten),
        seeds=jnp.asarray(seeds),
        windows=_windows(inten, seeds, T, words),
        t_total=jnp.asarray(T - 7 * (np.arange(b) % 5), jnp.int32),
        ltp_prob=jnp.asarray(np.where(np.arange(b) % 2, 1023, 16),
                             jnp.int32),
    )


def _window_ops(o):
    """(name, op, args, kwargs) for every window op of the main path."""
    from repro.kernels import ops

    train_kw = dict(**LIF, **SU)
    return [
        ("infer_window_batch", ops.infer_window_batch,
         (o["weights"], o["windows"]), dict(LIF)),
        ("infer_window_batch_encode", ops.infer_window_batch_encode,
         (o["weights"], o["inten"], o["seeds"]),
         dict(n_steps=T, t_total=o["t_total"], **LIF)),
        ("train_window_batch", ops.train_window_batch,
         (o["wts_b"], o["windows"], o["v_b"], o["lfsr_b"], o["teach_b"]),
         dict(ltp_prob=o["ltp_prob"], **train_kw)),
        ("train_window_batch_encode", ops.train_window_batch_encode,
         (o["wts_b"], o["inten"], o["seeds"], o["v_b"], o["lfsr_b"],
          o["teach_b"]),
         dict(n_steps=T, ltp_prob=o["ltp_prob"], **train_kw)),
        ("train_window_batch[B=1]", ops.train_window_batch,
         (o["wts_b"][:1], o["windows"][:1], o["v_b"][:1], o["lfsr_b"][:1],
          o["teach_b"][:1]), dict(ltp_prob=16, **train_kw)),
    ]


def phase_kernels(seed: int, backend: str = "tpu") -> None:
    """(b) every window op compiled for the chip and equal to the ref."""
    import jax

    o = _operands(N_NEURONS, B, seed)
    for name, op, args, kw in _window_ops(o):
        t0 = time.perf_counter()
        compiled = op.lower(*args, backend=backend, **kw).compile()
        t_compile = time.perf_counter() - t0
        custom = "tpu_custom_call" in compiled.as_text()
        got = jax.block_until_ready(op(*args, backend=backend, **kw))
        t0 = time.perf_counter()
        got = jax.block_until_ready(op(*args, backend=backend, **kw))
        t_wall = time.perf_counter() - t0
        want = op(*args, backend="ref", **kw)
        same = _equal(got, want)
        _line("b", op=name, backend=backend, shapes=_shapes(*args),
              compile_s=f"{t_compile:.3f}", wall_s=f"{t_wall:.6f}",
              tpu_custom_call=custom, equal_to_ref=same)
        assert backend != "tpu" or custom, f"{name}: no tpu_custom_call"
        assert same, f"{name}: {backend} differs from ref"


def _serve_requests(n_req: int, words: int, seed: int):
    """Intensity-only requests first (encode-kernel launches), then
    alternating intensity / pre-packed ones (mixed, host-encoded
    launches); ragged T <= 72."""
    import numpy as np

    from repro.core.encoder import encode_from_counter
    from repro.serving import SNNRequest

    inten, _ = _digits(n_req, seed)
    reqs = []
    for i in range(n_req):
        t_i = T - 8 * (i % 7) - (i % 3)
        if i >= n_req // 2 and i % 2:
            win = np.asarray(encode_from_counter(seed + i, inten[i], t_i))
            win = np.pad(win, ((0, 0), (0, words - win.shape[1])))
            reqs.append(SNNRequest(rid=i, window=win))
        else:
            reqs.append(SNNRequest(rid=i, intensities=inten[i],
                                   n_steps=t_i))
    return reqs


def _oracle_mismatches(eng, reqs, plan) -> int:
    """Requests whose counts differ from the ref path on their version's
    weights, checked in one batched ref launch per (version, T)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.encoder import encode_from_counter
    from repro.kernels import ops

    groups: dict = {}
    for r in reqs:
        if r.window is not None:
            win = np.asarray(r.window, np.uint32)
        else:
            win = np.asarray(encode_from_counter(
                r.seed, jnp.asarray(r.intensities), r.n_steps))
            win = np.pad(win, ((0, 0), (0, eng.words - win.shape[1])))
        groups.setdefault((r.served_version, win.shape[0]), []).append(
            (r, win))
    bad = 0
    for (ver, _), items in groups.items():
        version = eng.store.get(ver)
        if version is None:
            bad += len(items)
            continue
        want = np.asarray(ops.infer_window_batch(
            version.weights, jnp.asarray(np.stack([w for _, w in items])),
            threshold=plan.threshold, leak=plan.leak, backend="ref"))
        bad += sum(not np.array_equal(r.counts, want[j])
                   for j, (r, _) in enumerate(items))
    return bad


def _check_served(eng, reqs, plan) -> dict:
    st = eng.stats()
    served = sum(r.status == "SERVED" for r in reqs)
    mism = _oracle_mismatches(eng, reqs, plan)
    assert served == len(reqs), f"{len(reqs) - served} requests not SERVED"
    assert mism == 0, f"{mism} served counts differ from the ref oracle"
    for key in ("degraded", "degraded_launches", "integrity_failures",
                "canary_failures"):
        assert st[key] == 0, f"{key}={st[key]} (first error: " \
                             f"{eng.first_error})"
    assert eng.first_error is None, eng.first_error
    return st


def phase_serve(seed: int, n_req: int = 256, backend: str = "tpu") -> None:
    """(c) SNNServingEngine on the wall clock, oracle-checked."""
    import numpy as np

    from repro.configs.wenquxing_snn import WENQUXING_22A
    from repro.core.stdp import init_weights
    from repro.engine import plan_from_config
    from repro.serving import SNNServingEngine, SNNServingPolicy

    cfg = dataclasses.replace(WENQUXING_22A, encode="kernel")
    plan = dataclasses.replace(plan_from_config(cfg), max_batch=B)
    assert plan.kernel_backend == backend, (
        f"default kernel backend is {plan.kernel_backend}, not {backend}")
    weights = init_weights(cfg.n_neurons, cfg.words, density_seed=seed,
                           dense=False)
    eng = SNNServingEngine(
        weights, plan,
        neuron_class=np.tile(np.arange(cfg.n_classes), cfg.n_blocks),
        policy=SNNServingPolicy(max_retries=2, canary_every=4))
    reqs = _serve_requests(n_req, cfg.words, seed)
    t0 = time.perf_counter()
    eng.run(reqs)
    t_wall = time.perf_counter() - t0
    st = _check_served(eng, reqs, plan)
    _line("c", backend=plan.kernel_backend, encode=plan.encode,
          requests=len(reqs), shapes=f"w{N_NEURONS}x{cfg.words}",
          max_batch=plan.max_batch, batches=st["batches"],
          canary_checks=st["canary_checks"], degraded=st["degraded"],
          integrity_failures=st["integrity_failures"],
          wall_s=f"{t_wall:.3f}", oracle="equal")


def phase_train(seed: int, n_samples: int = 64,
                backend: str = "tpu") -> None:
    """(d) parallel-mode training on the kernels == on the reference."""
    import jax
    import numpy as np

    from repro.configs.wenquxing_snn import WENQUXING_22A_INTENSITY
    from repro.core import trainer
    from repro.data.digits import make_digits

    imgs, labels = make_digits(n_samples, seed=seed)
    cfg = dataclasses.replace(WENQUXING_22A_INTENSITY,
                              train_mode="parallel", epochs=1)
    assert cfg.plan().kernel_backend == backend, cfg.plan().kernel_backend
    models, walls = {}, {}
    for be in (backend, "ref"):
        t0 = time.perf_counter()
        m = trainer.train(dataclasses.replace(cfg, kernel_backend=be),
                          imgs, labels, key=jax.random.key(seed))
        models[be] = np.asarray(jax.block_until_ready(m.weights))
        walls[be] = time.perf_counter() - t0
    same = np.array_equal(models[backend], models["ref"])
    _line("d", backend=backend, mode="parallel", samples=n_samples,
          epochs=1, shapes=_shapes(models[backend]),
          wall_s=f"{walls[backend]:.3f}", ref_wall_s=f"{walls['ref']:.3f}",
          weights_equal_to_ref=same)
    assert same, f"trained weights on {backend} differ from ref"


def phase_mesh(seed: int, n: int = 4096, b: int = 256,
               backend: str = "tpu") -> None:
    """(four chips) sharded infer/train-batch ops == the one-chip result,
    every output spread over four devices; mesh-planned serving."""
    import jax
    import numpy as np

    from repro.distributed import snn_mesh
    from repro.engine import SNNEngine, SNNEnginePlan
    from repro.kernels import ops
    from repro.serving import SNNServingEngine

    o = _operands(n, b, seed)
    train_kw = dict(**LIF, **SU)
    cases = [
        ("infer_window_batch", ops.infer_window_batch,
         snn_mesh.sharded_infer_window_batch,
         (o["weights"], o["windows"]), dict(LIF)),
        ("infer_window_batch_encode", ops.infer_window_batch_encode,
         snn_mesh.sharded_infer_window_batch_encode,
         (o["weights"], o["inten"], o["seeds"]),
         dict(n_steps=T, t_total=o["t_total"], **LIF)),
        ("train_window_batch", ops.train_window_batch,
         snn_mesh.sharded_train_window_batch,
         (o["wts_b"], o["windows"], o["v_b"], o["lfsr_b"], o["teach_b"]),
         dict(ltp_prob=o["ltp_prob"], **train_kw)),
        ("train_window_batch_encode", ops.train_window_batch_encode,
         snn_mesh.sharded_train_window_batch_encode,
         (o["wts_b"], o["inten"], o["seeds"], o["v_b"], o["lfsr_b"],
          o["teach_b"]), dict(n_steps=T, ltp_prob=o["ltp_prob"],
                              **train_kw)),
    ]
    n_dev, n_req = 4, 8
    for name, op, sharded, args, kw in cases:
        t0 = time.perf_counter()
        one = jax.block_until_ready(op(*args, backend=backend, **kw))
        t_one = time.perf_counter() - t0
        for shape in MESHES:
            mesh = snn_mesh.snn_mesh2d(*shape)
            fn = jax.jit(lambda *a: sharded(*a, backend=backend, mesh=mesh,
                                            **kw))
            t0 = time.perf_counter()
            got = jax.block_until_ready(fn(*args))
            t_first = time.perf_counter() - t0
            t0 = time.perf_counter()
            got = jax.block_until_ready(fn(*args))
            t_wall = time.perf_counter() - t0
            spans = {len(x.sharding.device_set)
                     for x in jax.tree.leaves(got)}
            same = _equal(got, one)
            _line("mesh", op=name, backend=backend,
                  mesh=f"{shape[0]}x{shape[1]}", shapes=_shapes(*args),
                  one_chip_s=f"{t_one:.3f}",
                  compile_and_first_s=f"{t_first:.3f}",
                  wall_s=f"{t_wall:.6f}", devices_per_output=sorted(spans),
                  equal_to_one_chip=same)
            assert same, f"{name} on mesh {shape} differs from one chip"
            assert spans == {n_dev}, (
                f"{name} on mesh {shape}: outputs span {spans} devices")
    plan = SNNEnginePlan(n_syn=N_IN, w_exp=None, encode="kernel",
                         max_batch=n_req, mesh_shape=MESHES[-1],
                         kernel_backend=backend)
    counts = SNNEngine(plan).infer(o["weights"],
                                   intensities=o["inten"][:n_req],
                                   seeds=o["seeds"][:n_req], n_steps=T)
    spans = len(counts.sharding.device_set)
    eng = SNNServingEngine(o["weights"], plan)
    reqs = _serve_requests(n_req, int(o["weights"].shape[1]), seed)
    t0 = time.perf_counter()
    eng.run(reqs)
    t_wall = time.perf_counter() - t0
    st = _check_served(eng, reqs, plan)
    _line("mesh", op="SNNServingEngine", backend=backend,
          mesh="x".join(map(str, MESHES[-1])), requests=len(reqs),
          shapes=_shapes(o["weights"]), batches=st["batches"],
          infer_devices=spans, wall_s=f"{t_wall:.3f}", oracle="equal")
    assert spans == n_dev, f"mesh-planned infer spans {spans} devices"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Wenquxing 22A serve and train path on a TPU")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the (1,4)/(2,2) mesh path at 4096 "
                         "neurons, B=256, against one chip")
    ap.add_argument("--seed", type=int, default=0x22A)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":                                   # phase (a)
        print(f"chip_smoke: FAIL (a): JAX found platform {platform!r}, "
              "not a TPU; this smoke has no CPU fallback", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: FAIL (a): need {want} TPU chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    _line("a", platform=platform, kind=devices[0].device_kind.replace(
        " ", "_"), count=len(devices))
    phases = ([("mesh", phase_mesh)] if args.four_chips else
              [("b", phase_kernels), ("c", phase_serve),
               ("d", phase_train)])
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn(args.seed)
        except Exception:  # noqa: BLE001 — reported, and the run fails
            traceback.print_exc()
            failed.append(name)
            _line(name, status="FAIL",
                  wall_s=f"{time.perf_counter() - t0:.3f}")
            continue
        _line(name, status="ok", wall_s=f"{time.perf_counter() - t0:.3f}")
    if failed:
        print(f"chip_smoke: FAIL phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
