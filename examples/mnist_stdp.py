"""End-to-end driver for the paper's own experiment (Table 1 / Fig. 5).

Full pipeline: procedural digits (offline MNIST substitute) -> deskew +
soft threshold -> Poisson rate encoding -> supervised binary-stochastic-
STDP training with active learning -> test-set classification.

Run:  PYTHONPATH=src python examples/mnist_stdp.py \
          [--neurons 40] [--wexp 128] [--train 2000] [--test 1000] \
          [--kernel-backend ref|interp|tpu] [--train-mode active|parallel] \
          [--window-chunk T_CHUNK] [--encode host|kernel] [--mesh-shape D,N]

The backend/batching flags become one frozen ``SNNEnginePlan``
(``--train-mode parallel`` the batched training grid, ``--window-chunk``
the bounded-VMEM chunked spike streaming, ``--encode kernel`` the
intensity-resident ingestion where the dataset stays uint8 and spikes
are drawn in VMEM, ``--mesh-shape D,N`` the 2-D data × neuron
placement — needs D*N devices, e.g.
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` for 2,4), and
test-set classification runs the plan's ``SNNEngine.infer`` verb
directly — the same engine the trainer and the serving path dispatch
through.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.wenquxing_snn import WENQUXING_22A
from repro.core.encoder import (poisson_encode_batch,
                                quantize_intensities, sample_seeds)
from repro.core.preprocess import preprocess_batch
from repro.core.trainer import train
from repro.data.digits import make_digits
from repro.engine import SNNEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--neurons", type=int, default=40,
                    choices=[10, 20, 30, 40])
    ap.add_argument("--wexp", type=int, default=128)
    ap.add_argument("--train", type=int, default=2000)
    ap.add_argument("--test", type=int, default=1000)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--kernel-backend", default="ref",
                    choices=["ref", "interp", "tpu"],
                    help="window-kernel implementation (interp runs the "
                         "Pallas body in Python — slow, validation only)")
    ap.add_argument("--train-mode", default="active",
                    choices=["active", "parallel"],
                    help="active = sequential error-driven blocks, "
                         "parallel = all blocks in one batched grid")
    ap.add_argument("--window-chunk", type=int, default=None,
                    help="stream the spike window through VMEM in "
                         "chunks of this many cycles (kernel backends)")
    ap.add_argument("--encode", default="host",
                    choices=["host", "kernel"],
                    help="host = pre-encode the dataset into a spike "
                         "tensor (JAX PRNG), kernel = keep uint8 "
                         "intensities and draw spikes in VMEM from "
                         "counter-hash seeds")
    ap.add_argument("--mesh-shape", default=None, metavar="D,N",
                    help="shard every engine launch over a 2-D "
                         "(data × neuron) mesh; needs D*N devices")
    args = ap.parse_args()
    mesh_shape = (tuple(int(p) for p in args.mesh_shape.split(","))
                  if args.mesh_shape else None)

    print("rendering + preprocessing digits ...")
    imgs, labels = make_digits(args.train, seed=args.seed)
    timgs, tlabels = make_digits(args.test, seed=args.seed + 1)
    pp = lambda x: np.asarray(preprocess_batch(  # noqa: E731
        jnp.asarray(x.reshape(-1, 28, 28)), 0.1)).reshape(-1, 784)
    tr, te = pp(imgs), pp(timgs)

    cfg = dataclasses.replace(WENQUXING_22A, n_neurons=args.neurons,
                              w_exp=args.wexp, epochs=args.epochs,
                              kernel_backend=args.kernel_backend,
                              train_mode=args.train_mode,
                              window_chunk=args.window_chunk,
                              encode=args.encode,
                              mesh_shape=mesh_shape)
    print(f"training 784-{args.neurons} (w_exp={args.wexp}, "
          f"{args.epochs} epochs, {args.train} samples, "
          f"{args.train_mode}/"
          f"{args.kernel_backend}/{args.encode}"
          + (f"/mesh{mesh_shape}" if mesh_shape else "") + ") ...")
    t0 = time.time()
    model = train(cfg, tr, labels)
    print(f"  trained in {time.time() - t0:.1f}s")

    # classification = the engine's infer verb on the config's plan
    eng = SNNEngine(cfg.plan())
    if args.encode == "kernel":
        # test set stays intensity-resident too: uint8 rows + counter
        # seeds disjoint from the training chain
        counts = eng.infer(
            model.weights,
            intensities=quantize_intensities(jnp.asarray(te)),
            seeds=sample_seeds(0x7E57, len(te)), n_steps=cfg.n_steps)
    else:
        st = poisson_encode_batch(jax.random.key(99), jnp.asarray(te),
                                  cfg.n_steps)
        counts = eng.infer(model.weights, st)
    pred = model.neuron_class[jnp.argmax(counts, axis=-1)]
    acc = float(jnp.mean((pred == jnp.asarray(tlabels))
                         .astype(jnp.float32)))
    print(f"test accuracy: {acc:.4f}  "
          f"(paper, real MNIST @40: 0.9191; chance: 0.10)")

    from repro.core.bitpack import unpack
    on = np.asarray(unpack(model.weights, 784).sum(axis=1))
    print(f"effective synapses per neuron: mean={on.mean():.0f} "
          f"(w_exp budget = {args.wexp})")


if __name__ == "__main__":
    main()
