"""The unsupervised train loop: the trainer's ``train_mode="wta"`` chunk
step, one population trained one sample after another with lateral
inhibition and adaptive thresholds, its regfile carried from call to
call.

Set-up builds the one compiled step with its seeded state (the weights
and LFSR lanes as ``chip.train`` makes them, θ at 0) and drives it from
the seed through the first ``check_steps`` calls, each on a chunk of
its own; the window goes on with that same step and state over the
pool of chunks, one blocking call after another, until ``seconds``
have passed.  Each call reads back the program's two counters of its
samples, ``spikes`` and ``silent`` (samples with fewer than 5 spikes).
Once the window has closed, :mod:`chip.reference_wta` replays on the
chip, word for word, the first calls from the seeded state and
``window_checks`` calls of the window from the state each started from,
and the weights, LFSR lanes and θ are compared.  The control is that
reference without inhibition (``w_inh`` 0) in the program's place.
"""

from __future__ import annotations

import gc
import importlib
import sys
import time
import types

import numpy as np

from chip import reference_wta, traffic
from chip.record import Run
from chip.serve import memory_peak_bytes, trace_span
from chip.train import initial_state, window_picks


def program() -> types.SimpleNamespace:
    """The entry the window drives."""
    import repro.core  # noqa: F401  (initialises repro.engine's imports)
    from repro.core import trainer
    from repro.core.rvsnn import SnnRegFile

    return types.SimpleNamespace(
        SnnRegFile=SnnRegFile, SNNTrainConfig=trainer.SNNTrainConfig,
        wta_chunk_step=trainer.wta_chunk_step,
        train_wta_chunk=trainer.train_wta_chunk)


def _chunks(mix: dict, seed: int):
    """The pool of chunks: (intensities uint8[chunk, n_in], counter seeds
    uint32[chunk]) each, from the seed; intensities are the digit's
    pixels scaled by ``intensity_scale`` and rounded."""
    pool, _ = traffic.digit_pool(seed, mix["pool"])
    pool = np.round(pool * mix["intensity_scale"]).astype(np.uint8)
    return [(pool[traffic.pool_picks(seed, 0x7000 + c, mix["chunk"],
                                     len(pool))],
             traffic.counter_seeds(seed, 0x7100 + c, mix["chunk"]))
            for c in range(mix["pool_chunks"])]


def run(cfg: dict, mix: dict, seed: int, seconds: float, *, chips: int,
        peak: dict, t_start: float, tracer=None, prog=None) -> Run:
    import jax
    import jax.numpy as jnp

    p = prog if prog is not None else program()
    work = importlib.import_module(f"chip.work.{mix['kernel']}")
    rec = Run(cfg=cfg, mix=mix, chips=chips, peak=peak, kernel=mix["kernel"])
    t_steps, chunk = mix["t_steps"], mix["chunk"]
    step = p.wta_chunk_step(p.SNNTrainConfig(
        n_inputs=cfg["n_inputs"], n_neurons=cfg["n_neurons"],
        n_classes=cfg["n_classes"], n_steps=t_steps,
        threshold=cfg["threshold"], leak=cfg["leak"], w_exp=cfg["w_exp"],
        gain=cfg["gain"], ltp_prob=cfg["ltp_prob"], w_inh=cfg["w_inh"],
        theta_plus=cfg["theta_plus"], train_mode="wta", encode="kernel"))
    w0, lf0 = initial_state(cfg, seed)
    th0 = jnp.zeros(w0.shape[:2], jnp.int32)
    rfs = p.SnnRegFile(spike=jnp.zeros((1, w0.shape[-1]), jnp.uint32),
                       v=jnp.zeros(w0.shape[:2], jnp.int32), lfsr=lf0,
                       weights=w0, theta=th0)
    chunks = _chunks(mix, seed)
    feed = [(jnp.asarray(x), jnp.asarray(sd.astype(np.int32)))
            for x, sd in chunks]
    states = [_host(rfs)]
    for c in range(mix["check_steps"]):
        rfs, _, _ = p.train_wta_chunk(step, rfs, *feed[c])
        states.append(_host(rfs))

    gc.collect()
    gc.freeze()     # set-up's objects: no full collection walks them again
    if tracer is not None:
        tracer.start()
    t0 = time.perf_counter()
    rec.setup_s = t0 - t_start
    calls, k, spikes, silent = 0, mix["check_steps"], 0, 0
    trail = [rfs]       # the state before each window call, and the last
    with trace_span("window"):
        while True:
            with trace_span("train_call"):
                rfs, s, q = p.train_wta_chunk(step, rfs,
                                              *feed[k % len(feed)])
            trail.append(rfs)
            spikes += s
            silent += q
            k += 1
            calls += 1
            end = time.perf_counter()
            if end - t0 >= seconds:
                break
    rec.window_s = end - t0
    gc.unfreeze()
    if tracer is not None:
        rec.trace = tracer.stop()
    rec.memory_peak = memory_peak_bytes(chips)
    picked = window_picks(seed, calls, mix["window_checks"])
    starts = [_host(trail[j]) for j in picked]
    got = states[1:] + [_host(trail[j + 1]) for j in picked]
    used = [(mix["check_steps"] + j) % len(chunks) for j in picked]
    del rfs, feed, step, trail
    rec.completed = calls * chunk
    rec.attempted = calls
    rec.launches = calls * chunk          # one kernel launch per sample
    ops, nbytes = work.work(cfg, [t_steps])
    rec.ops, rec.nbytes = ops * rec.launches, nbytes * rec.launches
    rec.extra.update(spikes=spikes, silent=silent)
    print(f"train_wta window: samples {rec.completed} spikes {spikes} "
          f"silent {silent}", file=sys.stderr, flush=True)

    def replay(w_inh: int):
        out, state = [], states[0]
        for c in range(mix["check_steps"]):
            state = reference_call(cfg, mix, chunks[c], state, w_inh)
            out.append(state)
        return out + [reference_call(cfg, mix, chunks[c], st, w_inh)
                      for c, st in zip(used, starts)]

    want = replay(cfg["w_inh"])

    def judge(answers) -> dict:
        """Words of the packed weights, of the LFSR lanes and of θ, over
        all the states compared, that differ from the reference's."""
        return {f"{name}_words_differ": (
                    sum(int((a[i] != w[i]).sum())
                        for a, w in zip(answers, want)), 0)
                for i, name in enumerate(("weight", "lfsr", "theta"))}

    rec.checks = judge(got)
    # the control: the reference without inhibition, whatever the
    # intensity width control.py asks for
    rec.extra.update(judge=judge, control=lambda _bits: replay(0))
    return rec


def _host(rfs) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    return (np.asarray(rfs.weights[0]), np.asarray(rfs.lfsr[0]),
            np.asarray(rfs.theta[0]))


def reference_call(cfg, mix, chunk, state, w_inh: int):
    """The reference's (weights, lfsr, theta) after one call on
    ``chunk`` from ``state``, on the chip."""
    import jax.numpy as jnp

    x, sd = chunk
    out = reference_wta.train_stream(
        *(jnp.asarray(a) for a in state), jnp.asarray(x), jnp.asarray(sd),
        t_steps=mix["t_steps"], threshold=cfg["threshold"],
        leak=cfg["leak"], w_exp=cfg["w_exp"], gain=cfg["gain"],
        n_syn=cfg["n_inputs"], ltp_prob=cfg["ltp_prob"], w_inh=w_inh,
        theta_plus=cfg["theta_plus"])
    return tuple(np.asarray(a) for a in out)
