"""SNN network execution over the presentation window (paper §3.1).

.. deprecated::
    This module is a thin compatibility shim over the unified engine in
    :mod:`repro.engine` — build an
    :class:`~repro.engine.SNNEnginePlan` and speak the engine's three
    verbs (``infer`` / ``train`` / ``train_batch``) instead of threading
    ``cycle_backend``/``kernel_backend``/``window_chunk`` kwargs through
    these functions.  The wrappers stay byte-identical with the
    pre-engine implementations (see ``repro.engine`` for the migration
    table), so existing callers keep working unchanged.

The only logic that still lives here is the traced-parameter fallback:
engine plans hold concrete Python ints, so when a caller jits one of
these wrappers with ``LIFParams``/``STDPParams`` as runtime arguments
(tracers), the window path cannot lower them as kernel literals and the
wrapper drops to the original per-cycle ``lax.scan`` of ``snn_step``
calls.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.lif import LIFParams
from repro.core.rvsnn import SnnRegFile, snn_regfile, snn_step
from repro.core.stdp import STDPParams
from repro.engine import SNNEngine, SNNEnginePlan, SNNOutput
from repro.engine import engine as _engine
from repro.engine import reset_between_samples  # noqa: F401  (re-export)

__all__ = ["SNNOutput", "run_sample", "reset_between_samples",
           "infer_batch", "train_stream", "train_stream_batch"]


def _check_backend(cycle_backend: str) -> None:
    if cycle_backend not in ("window", "step"):
        raise ValueError(
            f"cycle_backend must be 'window' or 'step', got "
            f"{cycle_backend!r}")


def _static_int(x) -> int | None:
    """Concretize a parameter to a Python int, or None if traced."""
    try:
        return int(x)
    except (TypeError, jax.errors.ConcretizationTypeError):
        return None


def _make_plan(lif: LIFParams, stdp: STDPParams | None,
               kernel_backend: str | None, window_chunk: int | None
               ) -> SNNEnginePlan | None:
    """An engine plan from (possibly traced) params, or None if traced."""
    th, lk = _static_int(lif.threshold), _static_int(lif.leak)
    if th is None or lk is None:
        return None
    if stdp is None:
        return SNNEnginePlan(threshold=th, leak=lk, w_exp=None,
                             kernel_backend=kernel_backend,
                             t_chunk=window_chunk)
    su = tuple(_static_int(x) for x in
               (stdp.w_exp, stdp.gain, stdp.n_syn, stdp.ltp_prob))
    if any(x is None for x in su):
        return None
    return SNNEnginePlan(threshold=th, leak=lk, w_exp=su[0], gain=su[1],
                         n_syn=su[2], ltp_prob=su[3],
                         kernel_backend=kernel_backend,
                         t_chunk=window_chunk)


def run_sample(
    rf: SnnRegFile,
    spike_train: jnp.ndarray,   # uint32[T, w] packed input spikes
    lif: LIFParams,
    stdp: STDPParams | None = None,
    teach: jnp.ndarray | None = None,
    *,
    cycle_backend: str = "window",
    kernel_backend: str | None = None,
    window_chunk: int | None = None,
) -> SNNOutput:
    """Present one sample for T cycles.  stdp=None -> inference."""
    _check_backend(cycle_backend)
    plan = (_make_plan(lif, stdp, kernel_backend, window_chunk)
            if cycle_backend == "window" else None)
    if plan is not None:
        return SNNEngine(plan).train(rf, spike_train, teach)

    def body(carry: SnnRegFile, words: jnp.ndarray):
        carry, fired = snn_step(carry, words, lif, stdp, teach)
        return carry, fired

    rf_out, fired = jax.lax.scan(body, rf, spike_train)
    counts = jnp.sum(fired.astype(jnp.int32), axis=0)
    return SNNOutput(rf_out, counts, fired)


def infer_batch(
    weights: jnp.ndarray,       # uint32[n, w]
    spike_trains: jnp.ndarray,  # uint32[B, T, w]
    lif: LIFParams,
    *,
    cycle_backend: str = "window",
    kernel_backend: str | None = None,
    window_chunk: int | None = None,
) -> jnp.ndarray:
    """Spike counts int32[B, n] for a batch (weights frozen).

    Shim over :meth:`SNNEngine.infer`: the window path serves all B
    samples from ONE kernel launch; the step path (and the traced-lif
    fallback) vmaps B per-cycle scans.
    """
    _check_backend(cycle_backend)
    plan = (_make_plan(lif, None, kernel_backend, window_chunk)
            if cycle_backend == "window" else None)
    if plan is not None:
        return SNNEngine(plan).infer(weights, spike_trains)
    rf0 = snn_regfile(weights)

    def one(train):
        return run_sample(reset_between_samples(rf0), train, lif,
                          cycle_backend="step").spike_counts

    return jax.vmap(one)(spike_trains)


def train_stream(
    rf: SnnRegFile,
    spike_trains: jnp.ndarray,  # uint32[N, T, w] pre-encoded samples
    teach: jnp.ndarray,         # int32[N, n] per-sample teacher currents
    lif: LIFParams,
    stdp: STDPParams,
    *,
    cycle_backend: str = "window",
    kernel_backend: str | None = None,
    window_chunk: int | None = None,
) -> tuple[SnnRegFile, jnp.ndarray]:
    """Online STDP over a stream of samples (sequential, as in hardware).

    Shim over :func:`repro.engine.train_stream`.  Returns
    (rf', spike_counts int32[N, n]).
    """
    _check_backend(cycle_backend)
    plan = (_make_plan(lif, stdp, kernel_backend, window_chunk)
            if cycle_backend == "window" else None)
    if plan is not None:
        return _engine.train_stream(SNNEngine(plan), rf, spike_trains,
                                    teach)

    def body(carry: SnnRegFile, inp):
        train, tch = inp
        carry = reset_between_samples(carry)
        out = run_sample(carry, train, lif, stdp, tch,
                         cycle_backend=cycle_backend,
                         kernel_backend=kernel_backend,
                         window_chunk=window_chunk)
        return out.regfile, out.spike_counts

    return jax.lax.scan(body, rf, (spike_trains, teach))


def train_stream_batch(
    rfs: SnnRegFile,            # batched regfile (leading stream axis B)
    spike_trains: jnp.ndarray,  # uint32[B, N, T, w] per-stream samples
    teach: jnp.ndarray,         # int32[B, N, n] per-stream teachers
    lif: LIFParams,
    stdp: STDPParams,
    *,
    cycle_backend: str = "window",
    kernel_backend: str | None = None,
    window_chunk: int | None = None,
) -> tuple[SnnRegFile, jnp.ndarray]:
    """Online STDP over B independent streams, batched per launch.

    Shim over :func:`repro.engine.train_stream_batch` (one
    ``train_window_batch`` launch per presented sample).  Stream b is
    bit-exact (incl. its LFSR sequence) with
    ``train_stream(rf_b, spike_trains[b], teach[b], ...)``.  Falls back
    to a vmap of per-cycle scans when params arrive traced or
    ``cycle_backend="step"``.

    Returns (rfs', spike_counts int32[B, N, n]).
    """
    _check_backend(cycle_backend)
    plan = (_make_plan(lif, stdp, kernel_backend, window_chunk)
            if cycle_backend == "window" else None)
    if plan is not None:
        return _engine.train_stream_batch(SNNEngine(plan), rfs,
                                          spike_trains, teach)

    # scan over the sample axis: [B, N, ...] -> [N, B, ...]
    trains_t = jnp.swapaxes(spike_trains, 0, 1)
    teach_t = jnp.swapaxes(teach, 0, 1)

    def body(carry: SnnRegFile, inp):
        trains, tch = inp

        def one(rf_b, train_b, tch_b):
            out = run_sample(reset_between_samples(rf_b), train_b, lif,
                             stdp, tch_b, cycle_backend="step")
            return out.regfile, out.spike_counts

        return jax.vmap(one)(carry, trains, tch)

    rfs_out, counts = jax.lax.scan(body, rfs, (trains_t, teach_t))
    return rfs_out, jnp.swapaxes(counts, 0, 1)
