"""Fault-injection suite for the SNN serving robustness layer.

Covers the request lifecycle (structured rejection, backpressure,
deadlines, priorities), the bounded-retry + graceful-degradation
ladder, the output integrity guard + canary, and the seeded
FaultInjector storm acceptance criterion: every request terminates in
a terminal status, no exception escapes step()/run(), and every SERVED
count vector stays bit-exact with the host oracle.
"""

import dataclasses
import os
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.encoder import encode_from_counter
from repro.engine import SNNEnginePlan
from repro.kernels import ops
from repro.serving import (FaultInjectedError, FaultInjector, FaultSpec,
                           SNNRequest, SNNServingEngine, SNNServingPolicy,
                           degradation_ladder)

REPO = Path(__file__).resolve().parents[1]

N, W = 20, 4
PLAN = SNNEnginePlan(threshold=40, leak=3, w_exp=None, max_batch=3)
KPLAN = dataclasses.replace(PLAN, encode="kernel")


def _weights(seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, 2**32, (N, W), dtype=np.uint32))


def _request(rid, t_steps, seed=None, **kw):
    rng = np.random.default_rng(100 + rid if seed is None else seed)
    return SNNRequest(rid=rid, window=rng.integers(
        0, 2**32, (t_steps, W), dtype=np.uint32), **kw)


def _intensity_request(rid, t_steps, n_in=70, **kw):
    rng = np.random.default_rng(300 + rid)
    return SNNRequest(rid=rid, intensities=rng.integers(
        0, 256, (n_in,), dtype=np.uint8), n_steps=t_steps, **kw)


def _oracle(weights, r, plan):
    """Host-oracle counts for one request at its true window length."""
    if r.window is not None:
        win = np.asarray(r.window)
    else:
        win = np.asarray(encode_from_counter(
            r.seed, jnp.asarray(r.intensities), r.n_steps))
        win = np.pad(win, ((0, 0), (0, W - win.shape[1])))
    return np.asarray(ops.infer_window_batch(
        weights, jnp.asarray(win)[None], threshold=plan.threshold,
        leak=plan.leak, backend="ref"))[0]


class FailFirstN:
    """Deterministic hook: the first ``n`` hooked launches raise."""

    def __init__(self, n):
        self.n = n
        self.calls = 0

    def __call__(self, ctx):
        self.calls += 1
        if self.calls <= self.n:
            raise FaultInjectedError(f"boom #{self.calls}")
        return None


# --- degradation ladder -----------------------------------------------------

def test_degradation_ladder_rungs():
    # host + ref already: nothing to degrade to
    assert degradation_ladder(PLAN) == [PLAN]
    # kernel encode + ref backend: one host-encode rung below
    lad = degradation_ladder(KPLAN)
    assert [p.encode for p in lad] == ["kernel", "host"]
    # kernel encode + interp backend: full 3-rung ladder
    lad = degradation_ladder(
        dataclasses.replace(KPLAN, kernel_backend="interp"))
    assert [(p.encode, p.kernel_backend) for p in lad] == [
        ("kernel", "interp"), ("host", "interp"), ("host", "ref")]


# --- fault injector ---------------------------------------------------------

def test_fault_spec_validates():
    with pytest.raises(ValueError):
        FaultSpec(p_launch_error=1.5)
    with pytest.raises(ValueError):
        FaultSpec(error_burst=0)
    with pytest.raises(ValueError):
        FaultSpec(stall_ms=-1)


def test_fault_injector_is_deterministic():
    spec = FaultSpec(p_launch_error=0.3, p_corrupt=0.4, seed=5)
    ctx = {"step": 0, "level": 0, "kind": "serve", "batch_size": 3,
           "t_lens": [8, 8, 8]}

    def drive(inj, n=40):
        out = []
        for _ in range(n):
            try:
                out.append("corrupt" if inj(ctx) else "ok")
            except FaultInjectedError:
                out.append("error")
        return out

    a, b = drive(FaultInjector(spec)), drive(FaultInjector(spec))
    assert a == b
    assert "error" in a and "corrupt" in a     # storm actually storms


# --- retry / degradation ----------------------------------------------------

def test_launch_failure_retries_then_serves_bit_exact():
    weights = _weights(1)
    hook = FailFirstN(1)
    eng = SNNServingEngine(weights, PLAN,
                           policy=SNNServingPolicy(max_retries=2),
                           on_launch=hook)
    reqs = [_request(0, 10), _request(1, 7)]
    eng.run(reqs)
    assert [r.status for r in reqs] == ["SERVED", "SERVED"]
    assert eng.retried == 1 and eng.level == 0
    assert all(r.retries == 1 for r in reqs)
    for r in reqs:
        np.testing.assert_array_equal(r.counts, _oracle(weights, r, PLAN))


def test_retry_exhaustion_degrades_kernel_encode_to_host():
    weights = _weights(2)
    hook = FailFirstN(3)                 # rung 0's whole budget fails
    eng = SNNServingEngine(weights, KPLAN,
                           policy=SNNServingPolicy(max_retries=2),
                           on_launch=hook)
    reqs = [_intensity_request(i, 9) for i in range(3)]
    eng.run(reqs)
    assert all(r.status == "SERVED" for r in reqs)
    assert eng.level == 1 and eng.degraded == 1 and eng.retried == 2
    ev = eng.degradation_events[0]
    assert ev["encode"] == "host" and "launch failed" in ev["reason"]
    for r in reqs:                       # degraded path is bit-exact
        np.testing.assert_array_equal(r.counts,
                                      _oracle(weights, r, KPLAN))
    assert eng.stats()["degraded"] == 1


def test_degraded_launches_and_first_error_are_reported():
    """Launches served below rung 0 are counted, and the first contained
    error is kept for the run's report (the CLI fails a clean run on
    either)."""
    eng = SNNServingEngine(_weights(2), KPLAN,
                           policy=SNNServingPolicy(max_retries=2),
                           on_launch=FailFirstN(3))
    eng.run([_intensity_request(i, 9) for i in range(3)])
    assert eng.first_error == "FaultInjectedError: boom #1"
    assert eng.degraded_launches == 1
    assert eng.stats()["degraded_launches"] == 1
    clean = SNNServingEngine(_weights(2), KPLAN)
    clean.run([_intensity_request(i, 9) for i in range(3)])
    assert clean.first_error is None and clean.degraded_launches == 0


def test_failure_on_last_rung_marks_batch_failed_without_raising():
    weights = _weights(3)
    hook = FailFirstN(10**9)             # every launch dies
    eng = SNNServingEngine(weights, PLAN,   # 1-rung ladder
                           policy=SNNServingPolicy(max_retries=1),
                           on_launch=hook)
    reqs = [_request(0, 8), _request(1, 8)]
    eng.run(reqs)                        # must not raise
    assert [r.status for r in reqs] == ["FAILED", "FAILED"]
    assert all("boom" in r.error for r in reqs)
    assert all(r.counts is None for r in reqs)
    assert eng.stats()["failed"] == 2


# --- integrity guard / canary ----------------------------------------------

def test_corrupted_counts_repaired_by_oracle_fallback():
    weights = _weights(4)

    class CorruptFirst:
        calls = 0

        def __call__(self, ctx):
            self.calls += 1
            if self.calls == 1:
                return lambda c: np.where(
                    np.arange(len(c))[:, None] == 0, 10_000, np.array(c))
            return None

    eng = SNNServingEngine(weights, KPLAN, on_launch=CorruptFirst())
    reqs = [_intensity_request(i, 9) for i in range(3)]
    eng.run(reqs)
    assert all(r.status == "SERVED" for r in reqs)
    assert eng.integrity_failures == 1
    assert eng.level == 1                # corruption degrades the rung
    for r in reqs:
        np.testing.assert_array_equal(r.counts,
                                      _oracle(weights, r, KPLAN))


def test_canary_catches_in_range_corruption_and_degrades():
    weights = _weights(5)

    def hook(ctx):
        if ctx["kind"] == "canary":
            return lambda c: np.zeros_like(np.array(c))   # in-range, wrong
        return None

    pol = SNNServingPolicy(canary_every=1)
    eng = SNNServingEngine(weights, KPLAN, policy=pol, on_launch=hook)
    # the canary must be a non-trivial known answer for this check to
    # mean anything
    reqs = [_request(0, 8)]
    eng.run(reqs)
    assert reqs[0].status == "SERVED"
    assert (eng._canary_golden > 0).any()
    assert eng.canary_checks == 1 and eng.canary_failures == 1
    assert eng.level == 1                # in-range corruption caught
    assert eng.stats()["canary_failures"] == 1


def test_reprobe_returns_to_fast_path_after_healthy_steps():
    weights = _weights(6)
    hook = FailFirstN(1)
    pol = SNNServingPolicy(max_retries=0, reprobe_after=1)
    eng = SNNServingEngine(weights, KPLAN, policy=pol, on_launch=hook)
    eng.run([_intensity_request(0, 9)])
    assert eng.level == 1                # degraded on the first step
    eng.run([_intensity_request(1, 9)])  # healthy step at rung 1
    assert eng.level == 0                # re-probed the fast path
    assert eng.degradation_events[-1]["reason"].startswith("re-probe")


# --- admission: deadlines, backpressure, priorities -------------------------

def test_expired_deadline_drops_request_as_expired():
    eng = SNNServingEngine(_weights(7), PLAN)
    late = _request(0, 8, deadline_ms=0.0)
    fresh = _request(1, 8)
    eng.run([late, fresh])
    assert late.status == "EXPIRED" and "deadline" in late.error
    assert late.counts is None
    assert fresh.status == "SERVED"
    assert eng.stats()["expired"] == 1


def test_policy_default_deadline_applies_to_requests_without_one():
    pol = SNNServingPolicy(deadline_ms=0.0)
    eng = SNNServingEngine(_weights(8), PLAN, policy=pol)
    req = _request(0, 8)
    eng.run([req])
    assert req.status == "EXPIRED"


def test_backpressure_rejects_beyond_max_queue():
    pol = SNNServingPolicy(max_queue=2)
    eng = SNNServingEngine(_weights(9), PLAN, policy=pol)
    reqs = [_request(i, 8) for i in range(5)]
    admitted = [eng.submit(r) for r in reqs]
    assert admitted == [True, True, False, False, False]
    assert all(r.status == "REJECTED" and "backpressure" in r.error
               for r in reqs[2:])
    assert eng.stats()["rejected"] == 3
    eng.run(reqs)                        # queued two still complete
    assert [r.status for r in reqs[:2]] == ["SERVED", "SERVED"]


def test_priority_pulls_high_priority_requests_first():
    plan = dataclasses.replace(PLAN, max_batch=2)
    eng = SNNServingEngine(_weights(10), plan)
    r0, r1 = _request(0, 8), _request(1, 8)
    hi = _request(2, 8, priority=5)
    for r in (r0, r1, hi):
        eng.submit(r)
    eng.step()
    # first batch: the priority-5 request plus the oldest prio-0 one
    assert hi.status == "SERVED" and r0.status == "SERVED"
    assert r1.status == "QUEUED"
    eng.step()
    assert r1.status == "SERVED"


def test_latency_percentiles_recorded():
    eng = SNNServingEngine(_weights(11), PLAN)
    eng.run([_request(i, 8) for i in range(7)])
    st = eng.stats()
    assert st["service_ms_p99"] >= st["service_ms_p50"] > 0
    assert st["queue_wait_ms_p99"] >= st["queue_wait_ms_p50"] >= 0
    assert eng.service_hist.count == 7


# --- the storm acceptance criterion -----------------------------------------

def test_fault_storm_terminal_statuses_and_bit_exact_serves():
    """Seeded FaultInjector storm (launch failures + corrupted counts +
    expired deadlines): every request terminal, nothing raises, every
    SERVED vector bit-exact with the oracle, recovery counters nonzero."""
    weights = _weights(40)
    plan = dataclasses.replace(KPLAN, max_batch=4)
    pol = SNNServingPolicy(max_retries=1, canary_every=3,
                           reprobe_after=2)
    inj = FaultInjector(FaultSpec(p_launch_error=0.35, p_corrupt=0.5,
                                  error_burst=3, seed=11))
    eng = SNNServingEngine(weights, plan, policy=pol, on_launch=inj)
    reqs = []
    for i in range(24):
        if i % 6 == 5:                   # already-dead deadline
            reqs.append(_intensity_request(i, 9, deadline_ms=0.0))
        elif i % 2:
            reqs.append(_intensity_request(i, 9 - (i % 3)))
        else:
            reqs.append(_request(i, 10 - (i % 4), priority=i % 3))
    eng.run(reqs)

    assert all(r.terminal for r in reqs)
    assert sum(r.status == "EXPIRED" for r in reqs) == 4
    for r in reqs:
        if r.status == "SERVED":
            np.testing.assert_array_equal(r.counts,
                                          _oracle(weights, r, plan))
    st = eng.stats()
    assert st["retried"] > 0
    assert st["degraded"] > 0
    assert st["expired"] == 4
    assert st["integrity_failures"] > 0
    assert st["service_ms_p99"] >= st["service_ms_p50"] > 0
    assert inj.errors > 0 and inj.corruptions > 0


def test_launch_serve_snn_cli_fault_storm_smoke():
    """CI acceptance: serve --inject-faults terminates every request in
    a terminal status and degraded results stay bit-exact with the
    oracle (the CLI exits nonzero otherwise)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = (str(REPO / "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch",
         "wenquxing-snn", "--requests", "12", "--bench",
         "--inject-faults", "--fault-seed", "7"],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "non-terminal=0" in proc.stdout
    assert "oracle-check: ok" in proc.stdout
    assert "EXPIRED=2" in proc.stdout    # rids 4 and 9 carry deadline 0
    bench = dict(kv.split("=") for kv in
                 proc.stdout.split("serve-bench: ")[1].split())
    assert int(bench["retried"]) > 0
    assert int(bench["degraded"]) > 0
    assert int(bench["expired"]) == 2
    assert float(bench["service_ms_p99"]) > 0
