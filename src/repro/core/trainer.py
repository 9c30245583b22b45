"""Supervised STDP trainer + "Active learning" (paper §3.1).

10-neuron network: one neuron per digit class; a teacher current drives
the labeled neuron while the others are held at low activity (inhibited).

>10-neuron networks ("Active learning"): train 10 neurons, evaluate on
the training set, collect the misclassified samples, then train a fresh
block of 10 neurons *on the error samples only*, supervised by their
labels; repeat until the target population size.  Classification is by
the class of the maximally-firing neuron across all blocks.

``train_mode="parallel"`` instead trains ALL blocks concurrently on the
full training set — one ``engine.train_batch`` launch per presented
sample covers every block (per-block weights/v/LFSR regfiles,
decorrelated by per-block LFSR seeds) — trading the active-learning
curriculum for a B-way batched training grid.  ``ltp_prob`` rides along
as a per-stream SMEM scalar operand, so block 0 trains at the base
``ltp_prob`` while blocks >= 1 keep the faster ``ltp_prob_active``
schedule, exactly as in active mode.

``train_mode="wta"`` is unsupervised: Diehl & Cook's single population,
with no teacher current, learns by STDP while lateral inhibition
(``w_inh``) and adaptive thresholds (``theta_plus``, θ carried from
sample to sample) make its neurons compete and specialise; each neuron
is then labelled with the class it answers most.  It needs
``encode="kernel"``.

Ingestion is intensity-resident when ``encode="kernel"``: the dataset
is quantized ONCE to uint8[N, n_inputs] and stays that way — per-sample
seeds come from the counter hash (:func:`encoder.sample_seeds`) and
every presentation draws its spike window inside the window kernel, so
the N×T×w spike tensor never exists (n_inputs bytes/sample instead of
T*w*4 — T/8×, 16× at T=128).  ``encode="host"`` (the default) keeps
the legacy statistical pre-encode (``poisson_encode_batch`` with the
JAX PRNG) as the fallback path.

Placement: ``mesh_shape=(data, neurons)`` shards every engine launch
over a 2-D mesh — the block-stream/batch axis over "data", neuron rows
over "neurons" — making ``train_mode="parallel"`` a data-parallel sweep
whose weights never leave their devices.  Any factorization is
bit-exact with the unsharded run.

Execution (kernel path, backend, chunking, placement) is owned by the
unified engine: ``SNNTrainConfig.plan()`` builds the
:class:`~repro.engine.SNNEnginePlan` and everything below drives
:class:`~repro.engine.SNNEngine` verbs.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bitpack import n_words
from repro.core.encoder import (poisson_encode_batch,
                                quantize_intensities, sample_seeds,
                                sample_seeds_at)
from repro.core.rvsnn import snn_regfile, snn_regfile_batch
from repro.core.stdp import init_weights
from repro.engine import SNNEngine, plan_from_config
from repro.engine import engine as _engine


@dataclass(frozen=True)
class SNNTrainConfig:
    n_inputs: int = 784
    n_classes: int = 10
    n_neurons: int = 40          # total population (multiple of n_classes)
    n_steps: int = 72            # presentation window T (cycles/sample)
    threshold: int = 192         # streamlined-LIF firing threshold
    leak: int = 16               # per-cycle leak
    w_exp: int = 128             # paper meta-parameter {128, 256, 512}
    gain: int = 4                # homeostatic LTD slope
    ltp_prob: int = 16           # 10-bit stochastic-LTP prob (base block)
    ltp_prob_active: int = 1023  # faster LTP for active-learning blocks
                                 # (few, hard samples -> specialize)
    teach_pos: int = 64          # teacher current into the labeled neuron
    teach_neg: int = -1024       # inhibition into the others
    w_inh: int = 0               # lateral inhibition per spike (wta)
    theta_plus: int = 0          # threshold rise per spike (wta)
    epochs: int = 2
    seed: int = 0x22A
    kernel_backend: str | None = None  # "ref" | "interp" | "tpu"; None
                                       # = the platform's (see plan.py)
    train_mode: str = "active"      # "active" (sequential blocks on the
                                    # error set) | "parallel" (batched
                                    # training grid, all blocks at once)
                                    # | "wta" (one unsupervised
                                    # population, inhibition and θ)
    window_chunk: int | None = None  # VMEM spike-slab size (None = T)
    encode: str = "host"             # dataset ingestion: "host" keeps
                                     # the legacy JAX-PRNG pre-encode;
                                     # "kernel" holds uint8 intensities
                                     # and draws spikes in VMEM
    encode_seed: int = 0             # counter base for the in-kernel draw
    mesh_shape: tuple | None = None  # (data, neurons) 2-D placement of
                                     # every engine launch (None = local)

    @property
    def n_blocks(self) -> int:
        assert self.n_neurons % self.n_classes == 0
        return self.n_neurons // self.n_classes

    @property
    def words(self) -> int:
        return n_words(self.n_inputs)

    def plan(self, block_idx: int = 0, mesh=None):
        """The engine execution plan this config describes."""
        return plan_from_config(self, block_idx, mesh)


@dataclass
class SNNModel:
    """Trained population: packed weights + per-neuron class labels."""
    weights: jnp.ndarray           # uint32[n_neurons, w]
    neuron_class: jnp.ndarray      # int32[n_neurons]
    cfg: SNNTrainConfig = field(repr=False, default=None)


def _teacher(labels: jnp.ndarray, cfg: SNNTrainConfig) -> jnp.ndarray:
    """int32[N, n_classes] teacher currents for a 10-neuron block."""
    onehot = jax.nn.one_hot(labels, cfg.n_classes, dtype=jnp.int32)
    return onehot * cfg.teach_pos + (1 - onehot) * cfg.teach_neg


def _regfile_seed(key: jax.Array) -> int:
    """Fold a PRNG key into a nonzero 16-bit LFSR base seed."""
    return int(jax.random.randint(key, (), 1, 1 << 16))


def _train_block(cfg: SNNTrainConfig, key: jax.Array,
                 labels: jnp.ndarray, block_idx: int, *,
                 spike_trains: jnp.ndarray | None = None,
                 intensities: jnp.ndarray | None = None,
                 sample_idx: jnp.ndarray | None = None) -> jnp.ndarray:
    """Train one 10-neuron block online over (possibly repeated) samples.

    The sample stream is EITHER pre-encoded ``spike_trains``
    uint32[N, T, w] (``encode="host"``) OR uint8 ``intensities``
    [N, n_inputs] with their original dataset indices ``sample_idx``
    i32[N] — the intensity-resident path, where each presentation's
    window is drawn from the counter hash at use.  Counter seeds are
    epoch-keyed (``sample_seeds_at(encode_seed, idx, epoch)``), so each
    epoch re-presents the same samples with fresh Poisson draws at zero
    memory cost; epoch 0 is bit-exact with the historical derivation.
    ``key`` seeds the block's LFSR lanes (stochastic-STDP randomness),
    so per-block randomness is keyed; the default ``train()`` key chain
    is derived from ``cfg.seed``, keeping default-seed runs
    reproducible.
    """
    w0 = init_weights(cfg.n_classes, cfg.words, dense=True)
    rf = snn_regfile(w0, seed=_regfile_seed(key))
    teach = _teacher(labels, cfg)
    # The plan's params are plain ints closed over via the engine, so
    # they stay concrete at trace time and lower as kernel literals.
    eng = SNNEngine(cfg.plan(block_idx))
    if intensities is not None:
        step = jax.jit(functools.partial(_engine.train_stream, eng,
                                         n_steps=cfg.n_steps))
        for epoch in range(cfg.epochs):
            rf, _ = step(rf, teach=teach, intensities=intensities,
                         seeds=sample_seeds_at(cfg.encode_seed,
                                               sample_idx, epoch))
        return rf.weights
    step = jax.jit(functools.partial(_engine.train_stream, eng))
    for _ in range(cfg.epochs):
        rf, _ = step(rf, spike_trains, teach)
    return rf.weights


def _train_blocks_parallel(cfg: SNNTrainConfig, key: jax.Array,
                           labels: jnp.ndarray, *,
                           spike_trains: jnp.ndarray | None = None,
                           intensities: jnp.ndarray | None = None,
                           sample_idx: jnp.ndarray | None = None
                           ) -> jnp.ndarray:
    """Train all blocks concurrently on the full set (batched grid).

    Every presented sample is one ``engine.train_batch`` launch covering
    the B = n_blocks per-block regfiles; blocks differ by their keyed
    LFSR seeds AND their LTP schedule — ``ltp_prob`` is a per-stream
    SMEM scalar operand, so block 0 trains at the base ``ltp_prob`` and
    blocks >= 1 at ``ltp_prob_active``, matching active mode's
    ``cfg.plan(block_idx)`` schedule.  With ``cfg.mesh_shape`` the
    launch shards block streams over the "data" axis and neuron rows
    over "neurons" — the 2-D data-parallel training sweep.  The sample
    stream is pre-encoded windows OR uint8 intensities + their dataset
    indices ``sample_idx`` (shared across blocks, exactly as the
    broadcast spike trains were); counter seeds are epoch-keyed, so
    every epoch draws fresh windows.  Returns packed weights
    uint32[n_neurons, words].
    """
    b = cfg.n_blocks
    w0 = jnp.broadcast_to(
        init_weights(cfg.n_classes, cfg.words, dense=True),
        (b, cfg.n_classes, cfg.words))
    # blocks differ ONLY by these seeds, and lfsr.seed folds its base to
    # 16 bits — draw without replacement so no two blocks can collide
    # into bit-identical training runs
    lfsr_seeds = [int(s) + 1
                  for s in jax.random.choice(key, (1 << 16) - 1, (b,),
                                             replace=False)]
    rfs = snn_regfile_batch(w0, lfsr_seeds)
    teach = _teacher(labels, cfg)
    teach_b = jnp.broadcast_to(teach, (b,) + teach.shape)
    lp = jnp.asarray([cfg.ltp_prob if i == 0 else cfg.ltp_prob_active
                      for i in range(b)], jnp.int32)
    eng = SNNEngine(cfg.plan(0))
    if intensities is not None:
        inten_b = jnp.broadcast_to(intensities,
                                   (b,) + intensities.shape)
        step = jax.jit(functools.partial(_engine.train_stream_batch,
                                         eng, ltp_prob=lp,
                                         n_steps=cfg.n_steps))
        for epoch in range(cfg.epochs):
            rfs, _ = step(rfs, teach=teach_b, intensities=inten_b,
                          seeds=sample_seeds_at(cfg.encode_seed,
                                                sample_idx, epoch))
        return rfs.weights.reshape(b * cfg.n_classes, cfg.words)
    trains_b = jnp.broadcast_to(spike_trains, (b,) + spike_trains.shape)
    step = jax.jit(functools.partial(_engine.train_stream_batch, eng,
                                     ltp_prob=lp))
    for _ in range(cfg.epochs):
        rfs, _ = step(rfs, trains_b, teach_b)
    return rfs.weights.reshape(b * cfg.n_classes, cfg.words)


MIN_SPIKES = 5   # Diehl & Cook's least excitatory spikes a presentation


def wta_chunk_step(cfg: SNNTrainConfig):
    """The jitted chunk step of ``train_mode="wta"``: (rfs, intensities
    uint8[N, n_inputs], seeds i32[N]) -> (rfs', spikes, silent,
    recomputes).

    ``rfs`` is one population's regfile with a leading stream axis of 1.
    The N samples are presented one after another with no teacher
    current (``train_stream_batch``); v resets between them, and the
    weights, LFSR lanes and θ carry over.  ``spikes`` counts the
    population's spikes over the chunk, ``silent`` the samples that
    drew fewer than :data:`MIN_SPIKES` and ``recomputes`` the train
    kernel's (cycle, 128-neuron group) pairs with a spike, each of
    which recomputed that group's input currents; all three are reduced
    on the device.
    """
    eng = SNNEngine(cfg.plan())

    def chunk(rfs, intensities, seeds):
        rfs, counts, recomputes = _engine.train_stream_batch(
            eng, rfs, intensities=intensities[None], seeds=seeds,
            n_steps=cfg.n_steps, with_recomputes=True)
        per_sample = jnp.sum(counts[0], axis=-1)
        return (rfs, jnp.sum(per_sample),
                jnp.sum((per_sample < MIN_SPIKES).astype(jnp.int32)),
                jnp.sum(recomputes))

    return jax.jit(chunk)


def train_wta_chunk(step, rfs, intensities, seeds):
    """One call of a :func:`wta_chunk_step`, in an ``snn.train`` span
    with stats ``samples``, ``spikes``, ``silent`` and ``recomputes``,
    the three counters read to the host in one transfer; returns (rfs',
    spikes, silent)."""
    from repro.serving import spans

    with spans.span("train", samples=int(intensities.shape[0])) as sp:
        rfs, *counters = step(rfs, intensities, seeds)
        spikes, silent, recomputes = (int(c) for c in
                                      jax.device_get(counters))
        if sp is not None:
            sp.set_metadata(spikes=spikes, silent=silent,
                            recomputes=recomputes)
    return rfs, spikes, silent


def _train_wta(cfg: SNNTrainConfig, key: jax.Array, labels: jnp.ndarray,
               intensities: jnp.ndarray, sample_idx: jnp.ndarray
               ) -> SNNModel:
    """Unsupervised training of one population, then labels: each
    neuron takes the class whose samples it answers most (the serving
    path, which runs no inhibition)."""
    wk, lk = jax.random.split(key)
    w0 = init_weights(cfg.n_neurons, cfg.words,
                      density_seed=_regfile_seed(wk), dense=False)
    rfs = snn_regfile_batch(w0[None], [_regfile_seed(lk)])
    step = wta_chunk_step(cfg)
    for epoch in range(cfg.epochs):
        rfs, _, _ = train_wta_chunk(
            step, rfs, intensities,
            sample_seeds_at(cfg.encode_seed, sample_idx, epoch))
    weights = rfs.weights[0]
    counts = SNNEngine(cfg.plan()).infer(
        weights, intensities=intensities,
        seeds=sample_seeds(cfg.encode_seed, intensities.shape[0]),
        n_steps=cfg.n_steps)
    onehot = jax.nn.one_hot(labels, cfg.n_classes, dtype=jnp.int32)
    per_class = onehot.T @ counts                   # [n_classes, n]
    return SNNModel(weights, jnp.argmax(per_class, axis=0).astype(
        jnp.int32), cfg)


def classify(model: SNNModel, spike_trains: jnp.ndarray | None = None,
             *, intensities: jnp.ndarray | None = None,
             seeds=None) -> jnp.ndarray:
    """Predicted class int32[B]: class of the maximally-firing neuron.

    Takes pre-encoded ``spike_trains`` uint32[B, T, w] or uint8
    ``intensities`` [B, n_inputs] (+ per-sample ``seeds``), presented
    over ``cfg.n_steps`` cycles through the plan's encode path.
    """
    eng = SNNEngine(model.cfg.plan())
    if intensities is not None:
        counts = eng.infer(model.weights, intensities=intensities,
                           seeds=seeds, n_steps=model.cfg.n_steps)
    else:
        counts = eng.infer(model.weights, spike_trains)
    best = jnp.argmax(counts, axis=-1)
    return model.neuron_class[best]


def accuracy(model: SNNModel, spike_trains: jnp.ndarray | None = None,
             labels: jnp.ndarray | None = None, *,
             intensities: jnp.ndarray | None = None,
             seeds=None) -> float:
    pred = classify(model, spike_trains, intensities=intensities,
                    seeds=seeds)
    return float(jnp.mean((pred == labels).astype(jnp.float32)))


def train(cfg: SNNTrainConfig, images: np.ndarray, labels: np.ndarray,
          key: jax.Array | None = None) -> SNNModel:
    """Full active-learning training.

    images: float32[N, n_inputs] normalized (already preprocessed);
    labels: int[N].

    Dataset residency follows ``cfg.encode``: "host" pre-encodes the
    whole set into a uint32[N, T, w] spike tensor with the statistical
    JAX PRNG (the legacy fallback); "kernel" quantizes ONCE to
    uint8[N, n_inputs] + per-sample counter-hash seeds and every
    presentation draws its window inside the kernels — the N×T×w
    tensor is never materialized.  Kernel-path seeds are epoch-keyed
    (``sample_seeds(base, n, epoch)``): each training epoch re-presents
    the samples with fresh Poisson draws at zero memory cost, and epoch
    0 stays bit-exact with the historical seeds.
    """
    if cfg.train_mode not in ("active", "parallel", "wta"):
        raise ValueError(f"train_mode must be 'active', 'parallel' or "
                         f"'wta', got {cfg.train_mode!r}")
    if cfg.train_mode == "wta" and cfg.encode != "kernel":
        raise ValueError("train_mode='wta' needs encode='kernel'")
    if key is None:
        key = jax.random.key(cfg.seed)
    key, ek = jax.random.split(key)
    labels_j = jnp.asarray(labels, jnp.int32)

    if cfg.encode == "kernel":
        spike_trains = None
        intensities = quantize_intensities(
            jnp.asarray(images, jnp.float32))
        seeds = sample_seeds(cfg.encode_seed, intensities.shape[0])
        sample_idx = jnp.arange(intensities.shape[0], dtype=jnp.int32)
    else:
        spike_trains = poisson_encode_batch(
            ek, jnp.asarray(images, jnp.float32), cfg.n_steps)
        intensities = seeds = sample_idx = None

    if cfg.train_mode == "wta":
        return _train_wta(cfg, key, labels_j, intensities, sample_idx)
    if cfg.train_mode == "parallel":
        key, bk = jax.random.split(key)
        weights = _train_blocks_parallel(
            cfg, bk, labels_j, spike_trains=spike_trains,
            intensities=intensities, sample_idx=sample_idx)
        classes = jnp.tile(jnp.arange(cfg.n_classes, dtype=jnp.int32),
                           cfg.n_blocks)
        return SNNModel(weights, classes, cfg)

    blocks: list[jnp.ndarray] = []
    classes: list[jnp.ndarray] = []
    cur = (spike_trains, intensities, sample_idx, labels_j)
    for b in range(cfg.n_blocks):
        cur_trains, cur_inten, cur_idx, cur_labels = cur
        key, bk = jax.random.split(key)
        blocks.append(_train_block(
            cfg, bk, cur_labels, b, spike_trains=cur_trains,
            intensities=cur_inten, sample_idx=cur_idx))
        classes.append(jnp.arange(cfg.n_classes, dtype=jnp.int32))
        if b + 1 == cfg.n_blocks:
            break
        # Active learning: next block trains on this ensemble's errors.
        model = SNNModel(jnp.concatenate(blocks, axis=0),
                         jnp.concatenate(classes), cfg)
        if intensities is not None:
            pred = classify(model, intensities=intensities, seeds=seeds)
        else:
            pred = classify(model, spike_trains)
        err = np.asarray(pred != labels_j)
        if not err.any():
            break
        idx = np.where(err)[0]
        # error samples keep their ORIGINAL dataset indices: the same
        # (seed, epoch, intensity) derivation on every re-presentation
        if intensities is not None:
            cur = (None, intensities[idx], sample_idx[idx],
                   labels_j[idx])
        else:
            cur = (spike_trains[idx], None, None, labels_j[idx])
    return SNNModel(jnp.concatenate(blocks, axis=0),
                    jnp.concatenate(classes), cfg)
