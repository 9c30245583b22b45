"""The unified SNN engine: three verbs over one execution plan.

:class:`SNNEngine` is the single object that owns kernel-path and
placement decisions for the SNN stack.  Callers build one
:class:`~repro.engine.plan.SNNEnginePlan` and then speak three verbs:

``infer(weights, windows)``
    Spike counts i32[B, n] for B presentation windows, weights frozen,
    membrane reset per sample — the serving path.  Intensities under
    ``encode="kernel"`` take the MXU encode kernel
    (``infer_window_batch_encode``); everything else, the packed kernel
    (``infer_window_batch``) on host-encoded windows.  Either is sharded
    over the plan's mesh when it has one.

``train_batch(rfs, windows, teach)``
    B independent training streams in ONE launch (the batched training
    grid: ``train_window_batch``, or ``train_window_batch_encode`` for
    intensities under ``encode="kernel"``), with optional per-stream
    ``ltp_prob`` — the SMEM scalar operand keeps each stream's
    active-learning schedule.

``train(rf, window, teach)``
    One window to one register file: ``train_batch`` at B=1.

The module-level :func:`train_stream_batch` presents a stream of
samples to B streams, one ``train_batch`` launch a sample (v reset
between samples, a scan over the sample axis); :func:`train_stream` is
its one-stream case.  ``repro.core.trainer`` drives both.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.encoder import encode_from_counter, encode_windows_host
from repro.core.rvsnn import SnnRegFile, snn_regfile_batch
from repro.engine.plan import SNNEnginePlan
from repro.kernels import ops


class SNNOutput(NamedTuple):
    """One presented window: updated regfile + spike statistics."""
    regfile: SnnRegFile
    spike_counts: jnp.ndarray  # int32[n] output spikes over the window
    fired: jnp.ndarray         # bool[T, n] raster


def reset_between_samples(rf: SnnRegFile) -> SnnRegFile:
    """Clear membrane + spike registers, keep weights and LFSR (paper
    resets neuron state between digit presentations)."""
    return rf._replace(
        v=jnp.zeros_like(rf.v),
        spike=jnp.zeros_like(rf.spike),
    )


def _with_theta(rf: SnnRegFile) -> SnnRegFile:
    """``rf`` with a θ register: zeros (one per neuron) where it holds
    none."""
    if rf.theta is not None:
        return rf
    return rf._replace(theta=jnp.zeros_like(rf.v))


def _last_cycle_spikes(seeds, intensities, n_steps: int, words: int
                       ) -> jnp.ndarray:
    """Packed words of the window's final cycle (the spike register
    after a presentation), regenerated in isolation from the counter."""
    sd = jnp.asarray(seeds, jnp.uint32)
    rows = jax.vmap(
        lambda s, x: encode_from_counter(s, x, 1, t0=n_steps - 1)[0]
    )(jnp.broadcast_to(sd, intensities.shape[:1]), intensities)
    return jnp.pad(rows, ((0, 0), (0, words - rows.shape[-1])))


def _one(tree):
    """``tree`` with a leading stream axis of 1 on every leaf (None
    stays None)."""
    return jax.tree.map(lambda x: x[None], tree)


def _only(tree):
    """The one stream of ``tree``: every leaf's leading axis dropped."""
    return jax.tree.map(lambda x: x[0], tree)


def _one_of(windows, intensities, n_steps, what: str) -> None:
    if (windows is None) == (intensities is None):
        raise ValueError(f"{what}: pass exactly one of the packed "
                         "window(s) or intensities")
    if intensities is not None and n_steps is None:
        raise ValueError(f"{what}: n_steps is required with intensities")


class SNNEngine:
    """Dispatches the three verbs according to one frozen plan."""

    def __init__(self, plan: SNNEnginePlan):
        self.plan = plan

    def __repr__(self) -> str:
        return f"SNNEngine({self.plan!r})"

    # --- encoding --------------------------------------------------------

    def _seeds(self, seeds, b: int) -> jnp.ndarray:
        """Per-sample counter seeds (default: plan seed + sample index)."""
        if seeds is None:
            return self.plan.encode_seed + jnp.arange(b, dtype=jnp.int32)
        return jnp.broadcast_to(jnp.asarray(seeds, jnp.int32), (b,))

    # --- infer -----------------------------------------------------------

    def infer(self, weights: jnp.ndarray,
              windows: jnp.ndarray | None = None, *,
              intensities: jnp.ndarray | None = None, seeds=None,
              n_steps: int | None = None, t_total=None) -> jnp.ndarray:
        """Spike counts int32[B, n] for B presentation windows.

        Pass EITHER pre-packed ``windows`` uint32[B, T, w] OR uint8
        ``intensities`` [B, n_in] with ``n_steps`` (and optional
        per-sample ``seeds`` i32[B] / true lengths ``t_total`` i32[B]).
        The intensity form encodes deterministically from the counter —
        in VMEM when the plan says ``encode="kernel"`` (the window never
        exists in HBM), on the host otherwise — with identical counts
        either way.
        """
        p = self.plan
        mesh = p.placement()
        if intensities is not None or windows is None:
            _one_of(windows, intensities, n_steps, "infer")
            seeds = self._seeds(seeds, intensities.shape[0])
            if p.encode == "kernel":
                if mesh is not None:
                    from repro.distributed import snn_mesh
                    return snn_mesh.sharded_infer_window_batch_encode(
                        weights, intensities, seeds, n_steps=n_steps,
                        threshold=p.threshold, leak=p.leak,
                        t_total=t_total, t_chunk=p.t_chunk,
                        backend=p.kernel_backend, mesh=mesh)
                return ops.infer_window_batch_encode(
                    weights, intensities, seeds, n_steps=n_steps,
                    threshold=p.threshold, leak=p.leak, t_total=t_total,
                    t_chunk=p.t_chunk, backend=p.kernel_backend)
            windows = encode_windows_host(seeds, intensities, n_steps,
                                    weights.shape[1], t_total)
        if mesh is not None:
            from repro.distributed import snn_mesh
            return snn_mesh.sharded_infer_window_batch(
                weights, windows, threshold=p.threshold, leak=p.leak,
                t_chunk=p.t_chunk, backend=p.kernel_backend, mesh=mesh)
        return ops.infer_window_batch(
            weights, windows, threshold=p.threshold, leak=p.leak,
            t_chunk=p.t_chunk, backend=p.kernel_backend)

    # --- train -----------------------------------------------------------

    def train(self, rf: SnnRegFile, window: jnp.ndarray | None = None,
              teach: jnp.ndarray | None = None, *,
              intensities: jnp.ndarray | None = None, seed=None,
              n_steps: int | None = None) -> SNNOutput:
        """Present one window to one regfile: the one-stream case of
        :meth:`train_batch`.

        Pass EITHER a packed uint32[T, w] ``window`` OR uint8
        ``intensities`` [n_in] with ``n_steps`` (+ optional counter
        ``seed``; default: the plan's).  Needs a learning plan, as
        :meth:`train_batch` does.  Returns :class:`SNNOutput`.
        """
        return SNNOutput(*_only(self.train_batch(
            _one(rf), _one(window), _one(teach),
            intensities=_one(intensities), seeds=seed, n_steps=n_steps)))

    # --- train_batch -----------------------------------------------------

    def train_batch(self, rfs: SnnRegFile,
                    windows: jnp.ndarray | None = None,
                    teach: jnp.ndarray | None = None, *, ltp_prob=None,
                    intensities: jnp.ndarray | None = None, seeds=None,
                    n_steps: int | None = None,
                    with_recomputes: bool = False) -> tuple:
        """B independent streams, one launch: batched regfile (leading
        stream axis), windows uint32[B, T, w] OR intensities uint8
        [B, n_in] + ``n_steps`` (+ per-stream counter ``seeds`` i32[B]),
        teach i32[B, n].

        ``ltp_prob`` overrides the plan's shared value with a per-stream
        i32[B] vector (active-learning schedules per block).  Returns
        (rfs', spike_counts i32[B, n], fired bool[B, T, n]); stream b is
        bit-exact with a launch of regfile b alone.  Under a plan
        with lateral inhibition or adaptive thresholds,
        ``with_recomputes`` adds a fourth result: the coupled kernel's
        ``recomputes`` i32[B] (see
        :func:`repro.kernels.ops.train_window_batch_encode`).
        """
        p = self.plan
        if not p.learn:
            raise ValueError("train_batch needs a learning plan "
                             "(w_exp is None)")
        if with_recomputes and not p.wta:
            raise ValueError("with_recomputes needs lateral inhibition "
                             "or adaptive thresholds (w_inh, theta_plus)")
        lp = p.ltp_prob if ltp_prob is None else ltp_prob
        teach = (jnp.zeros_like(rfs.v) if teach is None
                 else teach.astype(jnp.int32))
        mesh = p.placement()
        if p.wta and intensities is None:
            raise ValueError("train_batch: lateral inhibition and "
                             "adaptive thresholds need intensities")
        if intensities is not None or windows is None:
            _one_of(windows, intensities, n_steps, "train_batch")
            seeds = self._seeds(seeds, intensities.shape[0])
            if p.encode == "kernel":
                kwargs = p.window_kwargs()
                theta = rfs.theta
                if p.wta:
                    theta = _with_theta(rfs).theta
                    kwargs.update(theta=theta, w_inh=p.w_inh,
                                  theta_plus=p.theta_plus)
                if mesh is not None:
                    from repro.distributed import snn_mesh
                    out = snn_mesh.sharded_train_window_batch_encode(
                        rfs.weights, intensities, seeds, rfs.v,
                        rfs.lfsr, teach, ltp_prob=lp, n_steps=n_steps,
                        t_chunk=p.t_chunk, backend=p.kernel_backend,
                        mesh=mesh, **kwargs)
                else:
                    out = ops.train_window_batch_encode(
                        rfs.weights, intensities, seeds, rfs.v,
                        rfs.lfsr, teach, ltp_prob=lp,
                        n_steps=n_steps, t_chunk=p.t_chunk,
                        backend=p.kernel_backend, **kwargs)
                w2, v2, fired, lf2 = out[:4]
                if p.wta:
                    theta = out[4]
                rfs_out = rfs._replace(
                    weights=w2, v=v2, lfsr=lf2, theta=theta,
                    spike=_last_cycle_spikes(seeds, intensities, n_steps,
                                             rfs.weights.shape[2]))
                counts = jnp.sum(fired.astype(jnp.int32), axis=1)
                if with_recomputes:
                    return rfs_out, counts, fired, out[5]
                return rfs_out, counts, fired
            windows = encode_windows_host(seeds, intensities, n_steps,
                                    rfs.weights.shape[2])
        kwargs = p.window_kwargs()
        if mesh is not None:
            from repro.distributed import snn_mesh
            w2, v2, fired, lf2 = snn_mesh.sharded_train_window_batch(
                rfs.weights, windows, rfs.v, rfs.lfsr, teach, ltp_prob=lp,
                t_chunk=p.t_chunk, backend=p.kernel_backend, mesh=mesh,
                **kwargs)
        else:
            w2, v2, fired, lf2 = ops.train_window_batch(
                rfs.weights, windows, rfs.v, rfs.lfsr, teach, ltp_prob=lp,
                t_chunk=p.t_chunk, backend=p.kernel_backend, **kwargs)
        rfs_out = rfs._replace(
            weights=w2, v=v2, lfsr=lf2,
            spike=windows[:, -1].astype(jnp.uint32))
        counts = jnp.sum(fired.astype(jnp.int32), axis=1)
        return rfs_out, counts, fired


# --- stream drivers (compose the verbs over the sample axis) ---------------

def train_stream(engine: SNNEngine, rf: SnnRegFile,
                 spike_trains: jnp.ndarray | None = None,
                 teach: jnp.ndarray | None = None, *,
                 intensities: jnp.ndarray | None = None, seeds=None,
                 n_steps: int | None = None
                 ) -> tuple[SnnRegFile, jnp.ndarray]:
    """Online STDP over a stream of samples (sequential, as in
    hardware): the one-stream case of :func:`train_stream_batch`.

    Pass EITHER pre-packed ``spike_trains`` uint32[N, T, w] OR uint8
    ``intensities`` [N, n_in] with ``n_steps`` and per-sample counter
    ``seeds`` i32[N] (default: the engine's seed chain); teach
    i32[N, n].  v resets between presentations; weights and LFSR
    persist.  Returns (rf', spike_counts i32[N, n]).
    """
    return _only(train_stream_batch(
        engine, _one(rf), _one(spike_trains), _one(teach),
        intensities=_one(intensities), seeds=seeds, n_steps=n_steps))


def train_stream_batch(engine: SNNEngine, rfs: SnnRegFile,
                       spike_trains: jnp.ndarray | None = None,
                       teach: jnp.ndarray | None = None, *,
                       ltp_prob=None,
                       intensities: jnp.ndarray | None = None,
                       seeds=None, n_steps: int | None = None,
                       with_recomputes: bool = False) -> tuple:
    """B independent sample streams, one :meth:`SNNEngine.train_batch`
    launch per presented sample.

    Pass EITHER ``spike_trains`` uint32[B, N, T, w] OR uint8
    ``intensities`` [B, N, n_in] with ``n_steps`` and per-sample
    ``seeds`` i32[N] (shared by every stream, as broadcast spike trains
    would be) or i32[B, N]; teach i32[B, N, n] (None: no teacher
    current).  ``ltp_prob`` optionally carries the per-stream i32[B]
    schedule through every launch.  v resets before every sample; the
    weights, LFSR lanes and, under a plan with lateral inhibition or
    adaptive thresholds, θ carry over.  Returns (rfs', spike_counts
    i32[B, N, n]); ``with_recomputes`` (intensities under such a plan)
    adds each launch's ``recomputes`` i32[B, N] as a third result.
    """
    _one_of(spike_trains, intensities, n_steps, "train_stream_batch")
    if engine.plan.wta:
        rfs = _with_theta(rfs)
    teach_t = None if teach is None else jnp.swapaxes(teach, 0, 1)
    if intensities is not None:
        b, n_samples = intensities.shape[:2]
        seeds = (engine._seeds(None, n_samples) if seeds is None
                 else jnp.asarray(seeds, jnp.int32))
        seeds = jnp.broadcast_to(seeds, (b, n_samples))
        inten_t = jnp.swapaxes(intensities, 0, 1)
        seeds_t = jnp.swapaxes(seeds, 0, 1)

        def body(carry: SnnRegFile, inp):
            x, s, tch = inp
            carry = carry._replace(v=jnp.zeros_like(carry.v))
            out = engine.train_batch(
                carry, teach=tch, ltp_prob=ltp_prob, intensities=x,
                seeds=s, n_steps=n_steps, with_recomputes=with_recomputes)
            return out[0], (out[1],) + out[3:]     # counts[, recomputes]

        rfs_out, per_sample = jax.lax.scan(body, rfs,
                                           (inten_t, seeds_t, teach_t))
        return (rfs_out,) + tuple(jnp.swapaxes(y, 0, 1)
                                  for y in per_sample)

    trains_t = jnp.swapaxes(spike_trains, 0, 1)

    def body(carry: SnnRegFile, inp):
        windows, tch = inp
        carry = carry._replace(v=jnp.zeros_like(carry.v))
        rfs2, counts, _ = engine.train_batch(carry, windows, tch,
                                             ltp_prob=ltp_prob)
        return rfs2, counts

    rfs_out, counts = jax.lax.scan(body, rfs, (trains_t, teach_t))
    return rfs_out, jnp.swapaxes(counts, 0, 1)


def refresh_weights(engine: SNNEngine, weights: jnp.ndarray, *,
                    labels: jnp.ndarray, n_classes: int,
                    teach_pos: int = 64, teach_neg: int = -1024,
                    intensities: jnp.ndarray | None = None, seeds=None,
                    n_steps: int | None = None,
                    spike_trains: jnp.ndarray | None = None,
                    lfsr_seeds=None, ltp_prob=None) -> jnp.ndarray:
    """One online-STDP refresh pass over a PACKED population bank — the
    train-while-serving verb.

    ``weights`` is a serving-shaped uint32[n, w] bank whose n =
    blocks × ``n_classes`` rows follow the block layout the trainer
    emits (neuron i's class is ``i % n_classes``).  The bank is
    reshaped into per-block regfiles and every labeled sample is one
    data-parallel :meth:`SNNEngine.train_batch` launch across all
    blocks — on the plan's mesh placement when one is present — then
    reshaped back, so a serving engine can periodically push live
    traffic (or a replay buffer) through the SU and obtain a refreshed
    *candidate* bank without ever mutating the serving copy.

    Samples are uint8 ``intensities`` [N, n_in] + counter ``seeds``
    i32[N] with ``n_steps`` (the intensity-resident form; pass
    epoch-keyed seeds for fresh draws per refresh) OR pre-packed
    ``spike_trains`` uint32[N, T, w].  ``teach_pos``/``teach_neg``
    build the supervision currents from ``labels`` exactly as the
    trainer does; ``lfsr_seeds`` (one per block, default a fixed
    decorrelated chain) key the stochastic-STDP lanes; ``ltp_prob``
    optionally carries a per-block i32[B] schedule.  Returns the
    refreshed bank uint32[n, w]; the input bank is never modified.
    """
    if not engine.plan.learn:
        raise ValueError("refresh_weights needs a learning plan "
                         "(w_exp is None)")
    n, w = int(weights.shape[0]), int(weights.shape[1])
    if n % n_classes:
        raise ValueError(f"weight bank rows ({n}) must be a multiple "
                         f"of n_classes ({n_classes})")
    b = n // n_classes
    w_b = jnp.asarray(weights, jnp.uint32).reshape(b, n_classes, w)
    if lfsr_seeds is None:
        # fixed decorrelated per-block chain (0x9E37 Weyl step, as
        # lfsr.seed uses internally); refresh determinism comes from
        # the caller's epoch-keyed sample seeds, not the LFSR bases
        lfsr_seeds = [(0x22A + 0x9E37 * i) & 0xFFFF or 0xACE1
                      for i in range(b)]
    rfs = snn_regfile_batch(w_b, lfsr_seeds)
    onehot = jax.nn.one_hot(jnp.asarray(labels, jnp.int32), n_classes,
                            dtype=jnp.int32)
    teach = onehot * teach_pos + (1 - onehot) * teach_neg
    teach_b = jnp.broadcast_to(teach, (b,) + teach.shape)
    if intensities is not None:
        inten_b = jnp.broadcast_to(intensities,
                                   (b,) + intensities.shape)
        rfs, _ = train_stream_batch(engine, rfs, teach=teach_b,
                                    ltp_prob=ltp_prob,
                                    intensities=inten_b, seeds=seeds,
                                    n_steps=n_steps)
    else:
        trains_b = jnp.broadcast_to(spike_trains,
                                    (b,) + spike_trains.shape)
        rfs, _ = train_stream_batch(engine, rfs, trains_b, teach_b,
                                    ltp_prob=ltp_prob)
    return rfs.weights.reshape(n, w)
