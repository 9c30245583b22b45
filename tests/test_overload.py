"""Overload-control tests: AIMD admission, CoDel sojourn management,
priority-aware shedding, the global retry budget, ladder circuit
breakers, and the engine integration (bit-identical replay, goodput
retention vs a naive engine, AIMD convergence on the virtual clock)."""

import dataclasses
import json
import math

import numpy as np
import pytest

from repro.core.stdp import init_weights
from repro.engine.plan import SNNEnginePlan
from repro.loadgen import (ArrivalSpec, WorkloadSpec, generate_rows,
                           scale_rows, u01)
from repro.loadgen.runner import (ServiceModel, VirtualClock, make_clock,
                                  rate_sweep, run_rows)
from repro.serving import (FaultInjector, FaultSpec, LadderBreakers,
                           OverloadController, OverloadPolicy, SNNRequest,
                           SNNServingEngine, SNNServingPolicy,
                           storm_policy)
from repro.serving.overload import (CLOSED, HALF_OPEN, OPEN,
                                    SHED_ADMISSION, SHED_CODEL,
                                    SHED_LOW_PRIORITY)


# --- policy validation -------------------------------------------------

@pytest.mark.parametrize("bad", [
    dict(slo_ms=0.0),
    dict(interval_ms=-1.0),
    dict(md_factor=1.0),
    dict(md_factor=0.0),
    dict(admit_rps_min=200.0, admit_rps_max=100.0),
    dict(admit_rps_init=10.0, admit_rps_min=50.0),
    dict(low_shed_start=0.9, low_shed_full=0.5),
    dict(low_shed_full=1.5),
    dict(high_reserve=-1.0),
    dict(max_sojourn_ms=0.0),
])
def test_policy_validation(bad):
    with pytest.raises(ValueError):
        OverloadPolicy(**bad)


def test_sojourn_limit_defaults_to_fraction_of_slo():
    assert OverloadPolicy(slo_ms=100.0).sojourn_limit_ms == 80.0
    assert OverloadPolicy(max_sojourn_ms=7.0).sojourn_limit_ms == 7.0


def test_storm_policy_scales_to_base_rate():
    p = storm_policy(10000.0)
    assert p.admit_rps_init == 20000.0
    assert p.admit_rps_min == 2500.0
    assert OverloadController(p).admit_rate == 20000.0


# --- AIMD token bucket -------------------------------------------------

def test_bucket_exhaustion_sheds_low_but_never_high():
    p = OverloadPolicy(burst=4.0, high_reserve=2.0, admit_rps_min=50.0,
                       admit_rps_max=50.0, low_shed_start=0.98,
                       low_shed_full=0.99)
    c = OverloadController(p)
    # burst 4, low needs 1 + reserve 2 = 3 tokens: two low admits fit
    # (4 -> 3 -> 2), the third finds the reserve breached
    assert c.admit(0, 0, 1024, now_ms=0.0) == (True, None)
    assert c.admit(0, 0, 1024, now_ms=0.0) == (True, None)
    ok, tag = c.admit(0, 0, 1024, now_ms=0.0)
    assert not ok and tag == SHED_ADMISSION
    # the high class bypasses the limiter even with the bucket drained
    for _ in range(10):
        ok, tag = c.admit(1, 0, 1024, now_ms=0.0)
        assert ok and tag is None
    assert c._tokens == 0.0          # high still drains what exists


def test_bucket_refills_at_admit_rate():
    p = OverloadPolicy(burst=8.0, high_reserve=0.0, admit_rps_min=1000.0,
                       admit_rps_max=1000.0, low_shed_start=0.98,
                       low_shed_full=0.99)
    c = OverloadController(p)
    for _ in range(8):
        assert c.admit(0, 0, 1024, now_ms=0.0)[0]
    assert not c.admit(0, 0, 1024, now_ms=0.0)[0]
    # 1000 rps = 1 token/ms: 5 ms restores 5 admits
    admits = sum(c.admit(0, 0, 1024, now_ms=5.0)[0] for _ in range(8))
    assert admits == 5


def test_aimd_decreases_on_congestion_increases_when_clean():
    p = OverloadPolicy(interval_ms=10.0, additive_rps=100.0,
                       md_factor=0.5, admit_rps_init=1000.0,
                       admit_rps_min=50.0, admit_rps_max=2000.0)
    c = OverloadController(p)
    c.admit(0, 0, 1024, now_ms=0.0)          # opens the interval
    c.note_served(p.slo_ms + 1.0)            # SLO breach -> congested
    c.admit(0, 0, 1024, now_ms=11.0)         # interval rolls: MD
    assert c.admit_rate == 500.0 and c.md_events == 1
    c.admit(0, 0, 1024, now_ms=22.0)         # clean interval: AI
    assert c.admit_rate == 600.0 and c.ai_events == 1
    # bucket exhaustion alone must NOT trigger MD
    c._tokens = 0.0
    assert not c.admit(0, 0, 1024, now_ms=23.0)[0]
    c.admit(0, 0, 1024, now_ms=33.0)
    assert c.admit_rate == 700.0             # still additive increase


# --- RED low-priority shed ---------------------------------------------

def test_red_shed_ramp_is_deterministic_and_monotone():
    p = OverloadPolicy(low_shed_start=0.25, low_shed_full=0.75,
                       admit_rps_min=1e6, admit_rps_max=1e6, burst=1e6)

    def shed_rate(occ):
        c = OverloadController(p)
        n = 200
        sheds = sum(c.admit(0, int(occ * 1000), 1000, now_ms=0.0)[1]
                    == SHED_LOW_PRIORITY for _ in range(n))
        return sheds / n

    assert shed_rate(0.2) == 0.0             # below the ramp
    mid, near_full = shed_rate(0.5), shed_rate(0.7)
    assert 0.2 < mid < 0.8 < near_full < 1.0
    assert shed_rate(0.75) == 1.0            # at/after full: always shed
    assert shed_rate(0.5) == mid             # same seed+counters: exact
    # the draw is the documented stateless counter hash
    c = OverloadController(p)
    ok, tag = c.admit(0, 500, 1000, now_ms=0.0)
    want_shed = u01(p.seed, 1, 1) < 0.5    # frac = (0.5-0.25)/(0.75-0.25)
    assert (tag == SHED_LOW_PRIORITY) == want_shed


def test_high_priority_skips_red_shed():
    p = OverloadPolicy(low_shed_start=0.1, low_shed_full=0.2)
    c = OverloadController(p)
    for _ in range(50):
        ok, tag = c.admit(1, 999, 1000, now_ms=0.0)
        assert ok and tag is None


# --- CoDel state machine -----------------------------------------------

def test_codel_arms_drops_and_exits():
    p = OverloadPolicy(target_sojourn_ms=5.0, interval_ms=100.0)
    c = OverloadController(p)
    assert c.on_dequeue(20.0, 0.0, 100) == 0      # arms first_above
    assert not c.dropping
    assert c.on_dequeue(20.0, 50.0, 100) == 0     # inside the interval
    n = c.on_dequeue(20.0, 101.0, 100)            # interval elapsed
    assert c.dropping and n >= 1 and c.codel_entries == 1
    # sqrt law: the first drop schedules the next interval/sqrt(1) out
    assert c._drop_next_ms == pytest.approx(101.0 + 100.0 / math.sqrt(1))
    # still dropping at t=350: drops 2..4 land interval/sqrt(k) apart
    # (201 + 100/sqrt(2) + 100/sqrt(3) ~ 329.4 <= 350 < +100/sqrt(4))
    n2 = c.on_dequeue(20.0, 350.0, 100)
    assert n2 == 3
    assert c._drop_next_ms == pytest.approx(
        201.0 + 100.0 / math.sqrt(2) + 100.0 / math.sqrt(3)
        + 100.0 / math.sqrt(4))
    # a single below-target observation resets everything
    assert c.on_dequeue(1.0, 150.0, 100) == 0
    assert not c.dropping and c._first_above_ms is None


def test_codel_drop_count_bounded_by_backlog():
    c = OverloadController(OverloadPolicy(target_sojourn_ms=1.0,
                                          interval_ms=10.0))
    c.on_dequeue(50.0, 0.0, 3)
    n = c.on_dequeue(50.0, 1000.0, 3)             # far past drop_next
    assert n <= 3                                 # never more than queued


# --- global retry budget -----------------------------------------------

def test_retry_budget_drains_and_refills():
    p = OverloadPolicy(retry_budget=2.0, retry_refill_per_s=1000.0)
    c = OverloadController(p)
    assert c.grant_retry(0.0) and c.grant_retry(0.0)
    assert not c.grant_retry(0.0)                 # exhausted
    assert c.grant_retry(1.5)                     # 1000/s: 1.5 tokens back
    assert not c.grant_retry(1.5)


# --- ladder breakers ---------------------------------------------------

def test_breaker_lifecycle():
    b = LadderBreakers(3)
    assert b.states() == [CLOSED] * 3
    b.open_rung(0)
    b.open_rung(0)                                # idempotent trip
    assert b.states() == [OPEN, CLOSED, CLOSED] and b.trips == 1
    b.open_rung(1)
    b.half_open_all()
    assert b.states() == [HALF_OPEN, HALF_OPEN, CLOSED]
    assert b.reprobes == 1
    b.close_trials()
    assert b.states() == [CLOSED] * 3
    b.open_rung(99)                               # out of range: ignored
    assert b.trips == 2
    # state round-trip (the journal snapshot path)
    b2 = LadderBreakers(3, states=[OPEN, HALF_OPEN, "bogus"])
    assert b2.states() == [OPEN, HALF_OPEN, CLOSED]


def test_controller_state_round_trip():
    c = OverloadController(OverloadPolicy(admit_rps_init=5000.0))
    c.admit(0, 10, 100, now_ms=3.0)
    c.on_dequeue(50.0, 4.0, 10)
    c.grant_retry(5.0)
    d = json.loads(json.dumps(c.state_dict()))    # JSON-safe
    c2 = OverloadController(c.policy)
    c2.load_state(d)
    assert c2.state_dict() == c.state_dict()
    c2.load_state({"unknown_future_key": 1})      # tolerated
    assert c2.state_dict() == c.state_dict()


# --- engine integration ------------------------------------------------

N_NEURONS = 32
N_INPUTS = 256


def _plan(max_batch=16):
    return SNNEnginePlan(threshold=192, leak=16, n_syn=N_INPUTS,
                         encode="kernel",
                         max_batch=max_batch, t_chunk=8)


def _engine(overload=None, injector=None, max_queue=512,
            deadline_ms=200.0):
    return SNNServingEngine(
        init_weights(N_NEURONS, N_INPUTS // 32, density_seed=0), _plan(),
        policy=SNNServingPolicy(max_queue=max_queue,
                                deadline_ms=deadline_ms),
        clock=VirtualClock(ServiceModel()), on_launch=injector,
        overload=overload)


def _specs(n, rate, high_frac=0.1):
    asp = ArrivalSpec(process="poisson", rate_rps=rate, n_requests=n,
                      seed=9)
    wl = WorkloadSpec(n_inputs=N_INPUTS, seed=4,
                      priority_choices=(0, 1),
                      priority_weights=(round(10 * (1 - high_frac)),
                                        round(10 * high_frac)))
    return asp, wl


def test_stats_keys_under_zero_traffic():
    """stats() must be fully populated before any request arrives."""
    eng = _engine(overload=OverloadPolicy(admit_rps_init=1234.0))
    st = eng.stats()
    assert st["admit_rate_rps"] == 1234.0
    for k in ("shed_admission", "shed_low_priority", "shed_codel",
              "retries_denied", "codel_entries", "aimd_md_events",
              "aimd_ai_events"):
        assert st[k] == 0
    assert st["codel_dropping"] is False
    assert st["retry_tokens"] == OverloadPolicy().retry_budget
    assert st["breaker_states"] == [CLOSED] * len(eng._plans)
    assert st["breaker_trips"] == 0
    # without a controller the overload keys are absent, the breaker
    # keys still present (pure observability, always on)
    bare = _engine().stats()
    assert "admit_rate_rps" not in bare
    assert bare["breaker_states"] == [CLOSED] * len(eng._plans)


def test_form_batch_expired_high_before_live_low():
    """A high-priority request whose deadline already elapsed must
    resolve EXPIRED at batch formation while a live low-priority
    request in the same queue still gets served."""
    eng = _engine()
    rng = np.random.default_rng(0)
    inten = rng.integers(0, 256, (N_INPUTS,), dtype=np.uint8)
    dead = SNNRequest(rid=0, intensities=inten, n_steps=8, priority=1,
                      deadline_ms=0.0)
    live = SNNRequest(rid=1, intensities=inten, n_steps=8, priority=0)
    eng.submit(dead)
    eng.submit(live)
    eng.clock.skip_to(eng.clock.now_ms() + 1.0)   # the deadline passes
    eng.step()
    assert dead.status == "EXPIRED"
    assert live.status == "SERVED"
    assert dead.shed is None                      # deadline, not a shed


def test_overload_none_engine_unchanged():
    """overload=None must leave the legacy pipeline bit-identical:
    no admission gate, no CoDel, no new stats keys."""
    asp, wl = _specs(n=300, rate=8000.0)
    rows = generate_rows(asp, wl)
    r1 = run_rows(_engine(), wl, rows, slo_ms=50.0)
    r2 = run_rows(_engine(), wl, rows, slo_ms=50.0)
    assert json.dumps(r1.to_dict(), sort_keys=True) == \
        json.dumps(r2.to_dict(), sort_keys=True)
    assert r1.per_status.get("REJECTED", 0) == 0


def test_overload_replay_bit_identical():
    asp, wl = _specs(n=600, rate=60000.0)         # well past capacity
    rows = generate_rows(asp, wl)

    def once():
        eng = _engine(overload=storm_policy(15000.0),
                      injector=FaultInjector(FaultSpec(
                          p_slowdown=0.05, slowdown_factor=3.0,
                          slowdown_steps=4, seed=3)))
        rep = run_rows(eng, wl, rows, slo_ms=50.0)
        return rep, eng.stats()

    (r1, s1), (r2, s2) = once(), once()
    assert json.dumps(r1.to_dict(), sort_keys=True) == \
        json.dumps(r2.to_dict(), sort_keys=True)
    assert {k: v for k, v in s1.items() if "ms" not in k} == \
        {k: v for k, v in s2.items() if "ms" not in k}
    assert r1.non_terminal == 0
    # overload shed mass exists and concentrates on the low class
    assert s1["shed_admission"] + s1["shed_low_priority"] \
        + s1["shed_codel"] > 0
    assert r1.slo_attainment_by_priority["1"] >= \
        r1.slo_attainment_by_priority["0"]


def test_controller_beats_naive_on_high_priority_under_overload():
    """Same 4x-overload stream: the controlled engine must keep the
    high class's SLO attainment where the naive engine loses it."""
    asp, wl = _specs(n=1200, rate=15000.0)
    rows = scale_rows(generate_rows(asp, wl), 4.0)  # ~60k rps offered
    naive = run_rows(_engine(), wl, rows, slo_ms=50.0)
    ctrl = run_rows(_engine(overload=storm_policy(15000.0)), wl, rows,
                    slo_ms=50.0)
    assert ctrl.non_terminal == naive.non_terminal == 0
    assert ctrl.slo_attainment_by_priority["1"] >= 0.95
    assert ctrl.slo_attainment_by_priority["1"] > \
        naive.slo_attainment_by_priority["1"]
    # every terminal is attributed exactly once across statuses
    assert sum(ctrl.per_status.values()) == len(rows)


def test_retry_denial_under_fault_burst():
    """A correlated launch-fault burst must hit the global retry budget
    and fail fast (retries_denied > 0) instead of retry-storming."""
    pol = OverloadPolicy(retry_budget=1.0, retry_refill_per_s=0.0)
    eng = _engine(overload=pol,
                  injector=FaultInjector(FaultSpec(
                      p_launch_error=0.9, error_burst=64, seed=11)))
    rng = np.random.default_rng(1)
    reqs = [SNNRequest(rid=i,
                       intensities=rng.integers(0, 256, (N_INPUTS,),
                                                dtype=np.uint8),
                       n_steps=8) for i in range(24)]
    eng.run(reqs)
    assert all(r.terminal for r in reqs)
    assert eng.retries_denied > 0
    # budget 1, no refill: at most one granted retry ever
    assert eng.retried <= 1


def test_aimd_converges_toward_sustainable_rate():
    """Property: under sustained overload on the virtual clock, the
    AIMD admission rate must end within the oscillation band of the
    independently-bisected sustainable rate — the limiter finds the
    capacity, it is not pinned at either rail."""
    asp, wl = _specs(n=3000, rate=1000.0, high_frac=0.0)

    def run_at(rate):
        rows = generate_rows(dataclasses.replace(asp, rate_rps=rate),
                             wl)
        return run_rows(_engine(), wl, rows, slo_ms=50.0)

    sustainable, _ = rate_sweep(run_at, 2000.0, 32000.0,
                                slo_floor=0.95, iters=5)
    assert 0.0 < sustainable < 32000.0
    rows = generate_rows(
        dataclasses.replace(asp, rate_rps=3.0 * sustainable,
                            n_requests=6000), wl)
    eng = _engine(overload=storm_policy(sustainable))
    run_rows(eng, wl, rows, slo_ms=50.0)
    rate = eng.stats()["admit_rate_rps"]
    p = eng.overload.policy
    assert p.admit_rps_min < rate < p.admit_rps_max   # off both rails
    # within the AIMD sawtooth band around capacity
    assert 0.3 * sustainable < rate < 1.7 * sustainable
    assert eng.stats()["aimd_md_events"] > 0
    assert eng.stats()["aimd_ai_events"] > 0


# --- rate_sweep degenerate edges ---------------------------------------

def test_rate_sweep_floor_unmet_at_lo_returns_zero_with_report():
    reports = {}

    def run_at(rate):
        class R:
            slo_attainment = 0.2
        reports[rate] = R()
        return reports[rate]

    rate, rep = rate_sweep(run_at, 500.0, 8000.0, slo_floor=0.95)
    assert rate == 0.0
    assert rep is reports[500.0]          # the lo report, not a dummy
    assert list(reports) == [500.0]       # no wasted probes past lo


def test_rate_sweep_floor_met_at_hi_returns_hi_with_report():
    calls = []

    def run_at(rate):
        calls.append(rate)
        class R:
            slo_attainment = 1.0
        return R()

    rate, rep = rate_sweep(run_at, 500.0, 8000.0, slo_floor=0.95)
    assert rate == 8000.0
    assert rep.slo_attainment == 1.0
    assert calls == [500.0, 8000.0]       # range was the binding limit
