"""The train loop: ``engine.train_stream_batch``, jitted as the trainer's
parallel mode jits it, carrying the regfiles from call to call.

Set-up builds the one compiled step with its state and drives it from
the seed through the first ``check_steps`` calls, each on a chunk of
its own; the window goes on with that same step and state over the
pool of chunks, one blocking call after another, until ``seconds``
have passed.  Once the window has closed the reference replays, word
for word, the first calls from the seeded initial state, and
``window_checks`` calls of the window from the state each started
from: the window's last call and others drawn from the seed.
"""

from __future__ import annotations

import functools
import gc
import importlib
import time
import types

import numpy as np

from chip import arrivals, reference, traffic
from chip.record import Run
from chip.serve import memory_peak_bytes, make_weights, trace_span


def program() -> types.SimpleNamespace:
    """The entry the window drives."""
    import repro.core  # noqa: F401  (initialises repro.engine's imports)
    from repro.core.rvsnn import SnnRegFile
    from repro.engine import SNNEngine, SNNEnginePlan
    from repro.engine.engine import train_stream_batch

    return types.SimpleNamespace(SNNEnginePlan=SNNEnginePlan,
                                 SNNEngine=SNNEngine, SnnRegFile=SnnRegFile,
                                 train_stream_batch=train_stream_batch)


def _chunks(cfg: dict, mix: dict, seed: int):
    """The pool of chunks: (intensities uint8[N, n_in], counter seeds
    uint32[N], teach int32[blocks, N, n]) each, from the seed."""
    pool, labels = traffic.digit_pool(seed, mix["pool"])
    blocks, n = cfg["blocks"], cfg["n_neurons"] // cfg["blocks"]
    out = []
    for c in range(mix["pool_chunks"]):
        picks = traffic.pool_picks(seed, 0x7000 + c, mix["chunk"], len(pool))
        onehot = np.eye(n, dtype=np.int32)[labels[picks] % n]
        teach = onehot * cfg["teach_pos"] + (1 - onehot) * cfg["teach_neg"]
        out.append((pool[picks], traffic.counter_seeds(seed, 0x7100 + c,
                                                       mix["chunk"]),
                    np.broadcast_to(teach, (blocks,) + teach.shape)))
    return out


def initial_state(cfg: dict, seed: int):
    """Weights uint32[blocks, n, words] with ``w_exp`` synapses on per
    row, and nonzero 16-bit LFSR lanes, made on the device."""
    import jax
    import jax.numpy as jnp

    blocks, n = cfg["blocks"], cfg["n_neurons"] // cfg["blocks"]
    w = make_weights(seed, blocks * n, cfg["n_inputs"], cfg["w_exp"])
    w = w.reshape(blocks, n, -1)
    key = jax.random.key(arrivals.u64(seed, 0x1F5B) & 0x7FFFFFFF)
    lfsr = jax.random.randint(key, w.shape, 1, 1 << 16, jnp.int32)
    return w, lfsr.astype(jnp.uint32)


def ltp_probs(cfg: dict) -> np.ndarray:
    """Block 0 at ``ltp_prob``, the others at ``ltp_prob_active``."""
    return np.array([cfg["ltp_prob"]] + [cfg["ltp_prob_active"]]
                    * (cfg["blocks"] - 1), np.int32)


def run(cfg: dict, mix: dict, seed: int, seconds: float, *, chips: int,
        peak: dict, t_start: float, tracer=None, prog=None) -> Run:
    import jax
    import jax.numpy as jnp

    p = prog if prog is not None else program()
    work = importlib.import_module(f"chip.work.{mix['kernel']}")
    rec = Run(cfg=cfg, mix=mix, chips=chips, peak=peak, kernel=mix["kernel"])
    blocks, t_steps, chunk = cfg["blocks"], mix["t_steps"], mix["chunk"]
    n = cfg["n_neurons"] // blocks
    w0, lf0 = initial_state(cfg, seed)
    rfs = p.SnnRegFile(spike=jnp.zeros((blocks, w0.shape[-1]), jnp.uint32),
                       v=jnp.zeros((blocks, n), jnp.int32), lfsr=lf0,
                       weights=w0)
    plan = p.SNNEnginePlan(threshold=cfg["threshold"], leak=cfg["leak"],
                           w_exp=cfg["w_exp"], gain=cfg["gain"],
                           n_syn=cfg["n_inputs"], ltp_prob=cfg["ltp_prob"],
                           encode="kernel")
    step = jax.jit(functools.partial(
        p.train_stream_batch, p.SNNEngine(plan),
        ltp_prob=jnp.asarray(ltp_probs(cfg)), n_steps=t_steps))
    chunks = _chunks(cfg, mix, seed)
    feed = [dict(teach=jnp.asarray(tc),
                 intensities=jnp.asarray(np.broadcast_to(
                     x, (blocks,) + x.shape)),
                 seeds=jnp.asarray(sd.astype(np.int32)))
            for x, sd, tc in chunks]
    states = [(np.asarray(w0), np.asarray(lf0))]
    for c in range(mix["check_steps"]):
        rfs, _ = step(rfs, **feed[c])
        states.append(_host(rfs))

    gc.collect()
    gc.freeze()     # set-up's objects: no full collection walks them again
    if tracer is not None:
        tracer.start()
    t0 = time.perf_counter()
    rec.setup_s = t0 - t_start
    calls, k = 0, mix["check_steps"]
    trail = [rfs]       # the state before each window call, and the last
    with trace_span("window"):
        while True:
            with trace_span("train_call"):
                rfs, _ = step(rfs, **feed[k % len(feed)])
                jax.block_until_ready(rfs)
            trail.append(rfs)
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
    ops, nbytes = work.work(cfg, [t_steps] * blocks)
    rec.ops, rec.nbytes = ops * rec.launches, nbytes * rec.launches

    def replay(in_bits: int):
        return (reference_states(cfg, mix, chunks, states[0], in_bits)
                + [reference_call(cfg, mix, chunks[c], st, in_bits)
                   for c, st in zip(used, starts)])

    want = replay(8)

    def judge(answers) -> dict:
        """Words of the packed weights and of the LFSR lanes, over all
        the states compared, that differ from the reference's."""
        return {"weight_words_differ": (
                    sum(int((a[0] != w[0]).sum())
                        for a, w in zip(answers, want)), 0),
                "lfsr_words_differ": (
                    sum(int((a[1] != w[1]).sum())
                        for a, w in zip(answers, want)), 0)}

    rec.checks = judge(got)
    rec.extra = {"judge": judge, "control": replay}
    return rec


def _host(rfs) -> tuple[np.ndarray, np.ndarray]:
    return np.asarray(rfs.weights), np.asarray(rfs.lfsr)


def window_picks(seed: int, calls: int, k: int) -> list[int]:
    """The window calls compared: the last, and ``k - 1`` others drawn
    from the seed (0-based, within the window)."""
    others = arrivals.rng(seed, 0xC411).permutation(max(calls - 1, 0))
    return sorted(set(others[:k - 1].tolist()) | {calls - 1})


def reference_call(cfg, mix, chunk, state, in_bits: int = 8):
    """The reference's (weights, lfsr) after one call on ``chunk`` from
    ``state``, on the host CPU (a long sequential scan)."""
    import jax

    x, sd, tc = chunk
    with jax.default_device(jax.devices("cpu")[0]):
        w, lf = reference.train_streams(
            state[0], state[1], x, sd, tc,
            ltp_probs(cfg).astype(np.uint32), t_steps=mix["t_steps"],
            threshold=cfg["threshold"], leak=cfg["leak"],
            w_exp=cfg["w_exp"], gain=cfg["gain"], n_syn=cfg["n_inputs"],
            in_bits=in_bits)
        return np.asarray(w), np.asarray(lf)


def reference_states(cfg, mix, chunks, state0, in_bits: int = 8):
    """The reference's (weights, lfsr) after each of the first
    ``check_steps`` chunks, each call starting from the last's."""
    out = []
    state = state0
    for chunk in chunks[:mix["check_steps"]]:
        state = reference_call(cfg, mix, chunk, state, in_bits)
        out.append(state)
    return out
