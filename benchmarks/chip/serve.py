"""The serve loop: ``SNNServingEngine.submit``/``step`` on the wall clock.

Open loop (``arrivals: poisson``): every request is stamped with its
intended arrival, submitted once that time has come, and timed from it,
so a stall delays the requests behind it instead of hiding.  After the
window's last arrival the loop serves what is queued (at most a minute).
Closed loop (``arrivals: backlog``): before each step the queue is
topped up to ``backlog`` x ``max_batch`` requests; the window ends with
the first step that completes after ``seconds``.

The answers due in the window, or a seeded sample of them, are compared
with the plain reference once the window has closed.
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

DRAIN_S = 60.0          # an answer later than this after the close never came
REF_BLOCK = 256         # reference rows per call (one compiled shape)


def program() -> types.SimpleNamespace:
    """The entry the window drives."""
    import repro.core  # noqa: F401  (initialises repro.engine's imports)
    from repro.engine import SNNEnginePlan
    from repro.serving.snn import SERVED, SNNRequest, SNNServingEngine

    return types.SimpleNamespace(SNNEnginePlan=SNNEnginePlan,
                                 SNNServingEngine=SNNServingEngine,
                                 SNNRequest=SNNRequest, SERVED=SERVED)


@functools.lru_cache(maxsize=None)
def _weights_fn(n: int, n_in: int, ones: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        u = jax.random.uniform(key, (n, n_in))
        kth = jnp.sort(u, axis=1)[:, ones - 1:ones]
        return reference.pack(u <= kth)

    return make


def make_weights(seed: int, n: int, n_in: int, ones: int):
    """Packed uint32[n, words] rows with ``ones`` synapses on each, made
    on the device in one jitted call from the seed."""
    import jax

    key = jax.random.key(arrivals.u64(seed, 0x3E16) & 0x7FFFFFFF)
    return _weights_fn(n, n_in, ones)(key)


def trace_span(name: str):
    import jax

    return jax.profiler.TraceAnnotation("bench." + name)


def run(cfg: dict, mix: dict, seed: int, seconds: float, *, chips: int,
        peak: dict, t_start: float, tracer=None, prog=None) -> Run:
    p = prog if prog is not None else program()
    work = importlib.import_module(f"chip.work.{mix['kernel']}")
    rec = Run(cfg=cfg, mix=mix, chips=chips, peak=peak, kernel=mix["kernel"])
    n, n_in, t_steps = cfg["n_neurons"], cfg["n_inputs"], mix["t_steps"]
    max_batch = cfg["max_batch"]
    weights = make_weights(seed, n, n_in, cfg["w_exp"])
    pool, _ = traffic.digit_pool(seed, mix["pool"])
    open_loop = mix["arrivals"] == "poisson"
    if open_loop:
        times = traffic.arrival_times(mix, seed, seconds)
        cap = len(times)
    else:
        cap = int(mix["max_per_s"] * seconds) + 4 * max_batch
    picks = traffic.pool_picks(seed, 0x9001, cap, len(pool))
    seeds = traffic.counter_seeds(seed, 0x5EED, cap)

    def make(i: int):
        return p.SNNRequest(rid=i, intensities=pool[picks[i]],
                            n_steps=t_steps, seed=int(seeds[i]))

    plan = p.SNNEnginePlan(threshold=cfg["threshold"], leak=cfg["leak"],
                           w_exp=None, n_syn=n_in, encode="kernel",
                           max_batch=max_batch)
    engine = p.SNNServingEngine(weights, plan)
    for j in range(max_batch):     # warm the one launch shape served
        r = make(j)
        r.rid = -1 - j
        engine.submit(r)
    engine.step()

    live: dict = {}                 # requests in flight, by id
    submitted = np.full(cap, np.nan)
    done = np.full(cap, np.nan)
    qwait = np.full(cap, np.nan)
    steps: list[float] = []
    kept = _Reservoir(mix["sample"], arrivals.rng(seed, 0xC4EC))
    t_lens = [t_steps] * max_batch

    def step(t0: float) -> float:
        with trace_span("step"):
            s0 = time.perf_counter()
            engine.step()
            s1 = time.perf_counter()
        steps.append(s1 - s0)
        finished = [j for j, r in live.items() if r.terminal]
        for j in finished:
            r = live.pop(j)
            if r.status == p.SERVED:
                done[j] = s1 - t0
                qwait[j] = r.queue_wait_ms
                kept.offer(j, r.counts)
        ops, nbytes = work.work(cfg, t_lens[:len(finished)])
        rec.launches += 1
        rec.ops += ops
        rec.nbytes += nbytes
        return s1

    gc.collect()
    gc.freeze()     # set-up's objects: no full collection walks them again
    if tracer is not None:
        tracer.start()
    t0 = time.perf_counter()
    rec.setup_s = t0 - t_start
    i = 0
    with trace_span("window"):
        if open_loop:
            deadline = t0 + seconds + DRAIN_S
            while True:
                now = time.perf_counter()
                if i < cap and t0 + times[i] <= now:
                    with trace_span("submit"):
                        while i < cap and t0 + times[i] <= now:
                            r = make(i)
                            r.t_submit_ms = (t0 + times[i]) * 1e3
                            submitted[i] = time.perf_counter() - t0
                            engine.submit(r)
                            live[i] = r
                            i += 1
                if now > deadline:
                    break
                if engine.queue:
                    step(t0)
                    continue
                if i >= cap:
                    break
                with trace_span("wait"):
                    target = t0 + times[i]
                    while time.perf_counter() < target:
                        if target - time.perf_counter() > 2e-3:
                            time.sleep(1e-3)
            rec.window_s = seconds
        else:
            backlog = mix["backlog"] * max_batch
            while True:
                with trace_span("submit"):
                    while len(engine.queue) < backlog and i < cap:
                        r = make(i)
                        submitted[i] = time.perf_counter() - t0
                        engine.submit(r)
                        live[i] = r
                        i += 1
                end = step(t0)
                if end - t0 >= seconds:
                    break
            rec.window_s = end - t0
    gc.unfreeze()
    if tracer is not None:
        rec.trace = tracer.stop()
    rec.memory_peak = memory_peak_bytes(chips)
    del engine

    if open_loop:       # every arrival of the window was offered
        ids = np.arange(cap)
    else:               # the backlog left queued at the close was not
        ids = np.array([j for j in range(i) if j not in live], np.int64)
    rec.intended_s = times[ids] if open_loop else submitted[ids]
    rec.submitted_s = submitted[ids]
    rec.done_s = done[ids]
    rec.queue_wait_ms = qwait[ids]
    rec.step_s = np.asarray(steps)
    served = ~np.isnan(done[ids])
    rec.attempted = len(ids)
    rec.failed = int((~served).sum())
    rec.completed = int(((done[ids] <= rec.window_s) & served).sum())

    sample, got = kept.items()
    inputs = {"intensities": pool[picks[sample]], "seeds": seeds[sample]}
    want = reference_counts(cfg, t_steps, weights, **inputs)

    def judge(counts: np.ndarray) -> dict:
        """Each number compared, beside its limit, for these answers to
        the sampled requests."""
        return {"count_mismatch": (int((counts != want).any(axis=1).sum()),
                                   0),
                "unserved": (rec.failed, 0)}

    rec.checks = judge(got)
    rec.extra = {"judge": judge,
                 "control": lambda in_bits: reference_counts(
                     cfg, t_steps, weights, in_bits=in_bits, **inputs)}
    return rec


def memory_peak_bytes(chips: int) -> int | None:
    """Peak bytes in use on the fullest chip used (None where the backend
    does not report it)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()[:chips]]
    peaks = [x for x in peaks if x is not None]
    return max(peaks) if peaks else None


class _Reservoir:
    """A seeded uniform sample of ``k`` served answers, kept as they come
    back, so the loop holds no request after its answer (and the
    collector has few objects to walk)."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k, self.rng, self.seen = k, rng, 0
        self.ids: list[int] = []
        self.counts: list[np.ndarray] = []

    def offer(self, j: int, counts) -> None:
        self.seen += 1
        if len(self.ids) < self.k:
            self.ids.append(j)
            self.counts.append(np.asarray(counts, np.int32))
            return
        slot = int(self.rng.integers(0, self.seen))
        if slot < self.k:
            self.ids[slot] = j
            self.counts[slot] = np.asarray(counts, np.int32)

    def items(self) -> tuple[np.ndarray, np.ndarray]:
        order = np.argsort(self.ids)
        return (np.asarray(self.ids, np.int64)[order],
                np.stack(self.counts)[order])


def reference_counts(cfg, t_steps, weights, *, intensities, seeds,
                     in_bits: int = 8) -> np.ndarray:
    """The reference's counts for given inputs, in blocks of rows."""
    import jax.numpy as jnp

    rows = len(intensities)
    out = []
    for s in range(0, rows, REF_BLOCK):
        e = min(rows, s + REF_BLOCK)
        pad = REF_BLOCK - (e - s)
        t_total = jnp.full((REF_BLOCK,), t_steps, jnp.int32)
        x = np.pad(intensities[s:e], ((0, pad), (0, 0)))
        sd = np.pad(seeds[s:e], (0, pad))
        c = reference.infer_counts_encoded(
            weights, jnp.asarray(x), jnp.asarray(sd), t_total,
            t_steps=t_steps, threshold=cfg["threshold"], leak=cfg["leak"],
            in_bits=in_bits)
        out.append(np.asarray(c)[:e - s])
    return np.concatenate(out)
