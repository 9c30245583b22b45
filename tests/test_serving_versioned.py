"""Versioned train-while-serving suite.

Covers the VersionedWeightStore lifecycle (stage / promote / swap /
rollback / restore), the probe-gated refresh path (corrupt candidates
caught at the fingerprint gate, regressions at the accuracy gate,
stalls at the timeout), crash-during-save recovery, and the refresh
storm acceptance criteria: every request terminal, every served
response attributable to a version promoted and live at serve time,
rollback/restart bit-exact with the last promoted checkpoint, and a
measurable probe-accuracy gain over frozen-weight serving.
"""

import dataclasses
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.encoder import encode_from_counter
from repro.engine import SNNEnginePlan
from repro.kernels import ops
from repro.serving import (FaultInjector, FaultSpec, SNNRefreshPolicy,
                           SNNRequest, SNNServingEngine, SNNServingPolicy,
                           SNNWeightRefresher, VersionedWeightStore,
                           weight_fingerprint)

N_CLASSES, BLOCKS, N_IN, W = 4, 2, 64, 2
N = N_CLASSES * BLOCKS
PLAN = SNNEnginePlan(threshold=24, leak=2, w_exp=128, n_syn=N_IN,
                     encode="kernel",
                     max_batch=4, t_chunk=8)
T = 16
NEURON_CLASS = np.tile(np.arange(N_CLASSES), BLOCKS)


def _weights(seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, 2**32, (N, W), dtype=np.uint32))


def _quadrant_data(n):
    """Linearly separable toy stream: class c lights up quadrant c."""
    labels = np.arange(n) % N_CLASSES
    inten = np.zeros((n, N_IN), np.uint8)
    for i, c in enumerate(labels):
        inten[i, c * 16:(c + 1) * 16] = 200
    return inten, labels


def _refresher(policy=None, **kw):
    inten, labels = _quadrant_data(64)
    return SNNWeightRefresher(
        PLAN, inten, labels, n_classes=N_CLASSES,
        probe_intensities=inten[:16], probe_labels=labels[:16],
        neuron_class=NEURON_CLASS, n_steps=T,
        policy=policy or SNNRefreshPolicy(refresh_every=2, probe_size=16,
                                          refresh_samples=16), **kw)


def _requests(n):
    inten, _ = _quadrant_data(64)
    return [SNNRequest(rid=i, intensities=inten[i % 64], n_steps=T)
            for i in range(n)]


def _oracle(weights, r):
    win = np.asarray(encode_from_counter(
        r.seed, jnp.asarray(r.intensities), r.n_steps))
    win = np.pad(win, ((0, 0), (0, W - win.shape[1])))
    return np.asarray(ops.infer_window_batch(
        weights, jnp.asarray(win)[None], threshold=PLAN.threshold,
        leak=PLAN.leak, backend="ref"))[0]


# --- store lifecycle --------------------------------------------------------


def test_store_seed_is_version_zero_and_live():
    st = VersionedWeightStore(_weights())
    assert st.serving.version == 0
    assert st.serving.origin == "seed"
    assert st.is_live(0)
    assert st.serving.verify()


def test_store_stage_is_monotonic_and_invisible():
    st = VersionedWeightStore(_weights())
    c1 = st.stage(_weights(1))
    c2 = st.stage(_weights(2))
    assert (c1.version, c2.version) == (1, 2)
    assert st.serving.version == 0          # staging never swaps
    assert not st.is_live(1) and not st.is_live(2)


def test_store_promote_swaps_only_at_swap_point():
    st = VersionedWeightStore(_weights())
    cand = st.stage(_weights(1))
    assert st.promote(cand)
    # promotion queues the swap; traffic still sees the old version
    assert st.serving.version == 0
    assert st.swap_if_pending()
    assert st.serving.version == 1
    assert not st.swap_if_pending()         # idempotent once applied
    assert st.is_live(1)


def test_store_promote_refuses_corrupt_candidate():
    st = VersionedWeightStore(_weights())
    cand = st.stage(_weights(1))
    bad = dataclasses.replace(
        cand, weights=jnp.asarray(np.asarray(cand.weights) ^ 1,
                                  jnp.uint32))
    assert not bad.verify()
    with pytest.raises(ValueError, match="fingerprint"):
        st.promote(bad)


def test_store_rollback_in_memory():
    st = VersionedWeightStore(_weights())
    st.promote(st.stage(_weights(1)))
    st.swap_if_pending()
    tgt = st.rollback(reason="test")
    assert tgt.version == 0 and tgt.origin == "rollback"
    st.swap_if_pending()
    assert st.serving.version == 0
    assert not st.is_live(1)                # demoted, never serveable
    np.testing.assert_array_equal(np.asarray(st.serving.weights),
                                  np.asarray(_weights()))
    assert st.rollback() is None            # nothing left to fall to


def test_store_rollback_reads_checkpoint_bit_exact(tmp_path):
    st = VersionedWeightStore(_weights(), state_dir=tmp_path)
    w1 = _weights(1)
    st.promote(dataclasses.replace(st.stage(w1), probe_accuracy=0.75))
    st.swap_if_pending()
    w2 = _weights(2)
    st.promote(st.stage(w2))
    st.swap_if_pending()
    tgt = st.rollback(reason="post-promotion regression")
    st.swap_if_pending()
    assert st.serving.version == 1
    assert st.serving.probe_accuracy == 0.75     # round-tripped
    np.testing.assert_array_equal(np.asarray(st.serving.weights),
                                  np.asarray(w1))
    assert tgt.fingerprint == weight_fingerprint(w1)
    # the demoted version's checkpoint is gone: restart converges with
    # post-rollback serving, never the rolled-back bank
    assert not (tmp_path / "step_2").exists()
    st2 = VersionedWeightStore(_weights(), state_dir=tmp_path)
    assert st2.serving.version == 1
    np.testing.assert_array_equal(np.asarray(st2.serving.weights),
                                  np.asarray(w1))


def test_store_restart_restores_newest_complete(tmp_path):
    st = VersionedWeightStore(_weights(), state_dir=tmp_path)
    w3 = _weights(3)
    st.promote(st.stage(w3))
    # a crashed writer's dropping must be ignored AND purged
    torn = tmp_path / "step_9.tmp"
    torn.mkdir()
    (torn / "weights.proc0.npy").write_bytes(b"torn")
    st2 = VersionedWeightStore(_weights(7), state_dir=tmp_path)
    assert st2.serving.version == 1
    assert st2.serving.origin == "restore"
    np.testing.assert_array_equal(np.asarray(st2.serving.weights),
                                  np.asarray(w3))
    assert not torn.exists()


def test_store_save_crash_aborts_promotion(tmp_path):
    st = VersionedWeightStore(_weights(), state_dir=tmp_path)

    def crash(ctx):
        assert ctx["kind"] == "save"
        raise RuntimeError("power loss")

    assert not st.promote(st.stage(_weights(1)), on_save=crash)
    assert st.serving.version == 0
    assert not st.swap_if_pending()         # nothing became swappable
    assert st.save_crashes == 1
    assert (tmp_path / "step_1.tmp").exists()
    assert not (tmp_path / "step_1").exists()
    # a restarted process sees only the complete seed checkpoint
    st2 = VersionedWeightStore(_weights(9), state_dir=tmp_path)
    assert st2.serving.version == 0
    np.testing.assert_array_equal(np.asarray(st2.serving.weights),
                                  np.asarray(_weights()))


# --- refresher --------------------------------------------------------------


def test_refresher_probe_is_pure_function_of_weights():
    rf = _refresher()
    w = _weights()
    assert rf.probe(w) == rf.probe(w)


def test_refresher_epochs_key_fresh_draws():
    rf = _refresher(policy=SNNRefreshPolicy(refresh_every=1,
                                            probe_size=16,
                                            refresh_samples=64))
    w = _weights()
    c1, e1 = rf.next_candidate(w)
    c2, e2 = rf.next_candidate(w)
    assert (e1, e2) == (0, 1)
    # full cyclic pass each time -> same samples, different epochs ->
    # different windows/LFSR chains -> different candidates
    assert not np.array_equal(np.asarray(c1), np.asarray(c2))


def test_refresher_requires_learning_plan():
    inten, labels = _quadrant_data(8)
    with pytest.raises(ValueError, match="learning plan"):
        SNNWeightRefresher(
            dataclasses.replace(PLAN, w_exp=None), inten, labels,
            n_classes=N_CLASSES, probe_intensities=inten,
            probe_labels=labels, neuron_class=NEURON_CLASS, n_steps=T)


# --- engine integration -----------------------------------------------------


def test_refresh_serving_improves_probe_accuracy():
    rf = _refresher()
    eng = SNNServingEngine(_weights(), PLAN, neuron_class=NEURON_CLASS,
                           refresher=rf)
    out = eng.run(_requests(40))
    st = eng.stats()
    assert all(r.terminal for r in out)
    assert st["versions_promoted"] >= 1
    assert st["version_violations"] == 0
    assert rf.probe(eng.weights) > rf.probe(_weights())
    # served versions advance monotonically with rid (promotions only
    # land between steps) and all come from the promotion history
    served = [r for r in out if r.status == "SERVED"]
    vs = [r.served_version for r in sorted(served, key=lambda r: r.rid)]
    assert vs == sorted(vs)
    assert all(v in eng.store.promoted_order for v in vs)


def test_served_counts_bit_exact_with_served_version_oracle():
    eng = SNNServingEngine(_weights(), PLAN, neuron_class=NEURON_CLASS,
                           refresher=_refresher(), keep_versions=64)
    out = eng.run(_requests(32))
    served = [r for r in out if r.status == "SERVED"]
    assert len({r.served_version for r in served}) > 1   # swaps happened
    for r in served:
        ver = eng.store.get(r.served_version)
        np.testing.assert_array_equal(
            r.counts, _oracle(ver.weights, r),
            err_msg=f"rid={r.rid} version={r.served_version}")


def test_corrupt_candidates_always_caught_at_probe_gate():
    inj = FaultInjector(FaultSpec(seed=3, p_refresh_corrupt=1.0))
    eng = SNNServingEngine(_weights(), PLAN, neuron_class=NEURON_CLASS,
                           refresher=_refresher(), on_launch=inj)
    out = eng.run(_requests(32))
    st = eng.stats()
    assert all(r.terminal for r in out)
    assert st["refresh_runs"] >= 3
    # every corrupted candidate was staged, then rejected at the
    # fingerprint gate — none promoted, traffic never saw one
    assert st["refresh_corrupt"] == st["refresh_runs"] \
        == inj.refresh_corruptions
    assert st["versions_promoted"] == 0
    assert st["weight_version"] == 0
    assert {r.served_version for r in out if r.status == "SERVED"} == {0}
    np.testing.assert_array_equal(np.asarray(eng.weights),
                                  np.asarray(_weights()))


def test_stalled_refresh_hits_timeout_and_never_promotes():
    inj = FaultInjector(FaultSpec(seed=3, p_refresh_stall=1.0,
                                  refresh_stall_ms=30.0))
    pol = SNNRefreshPolicy(refresh_every=2, probe_size=16,
                           refresh_samples=16, refresh_timeout_ms=1e-3)
    eng = SNNServingEngine(_weights(), PLAN, neuron_class=NEURON_CLASS,
                           refresher=_refresher(policy=pol),
                           on_launch=inj)
    eng.run(_requests(16))
    st = eng.stats()
    assert st["refresh_timeouts"] == st["refresh_runs"] >= 1
    assert st["versions_promoted"] == 0
    assert inj.refresh_stalls == st["refresh_runs"]


def test_save_crash_leaves_serving_on_old_version(tmp_path):
    inj = FaultInjector(FaultSpec(seed=3, p_save_crash=1.0))
    eng = SNNServingEngine(_weights(), PLAN, neuron_class=NEURON_CLASS,
                           refresher=_refresher(), on_launch=inj,
                           state_dir=tmp_path)
    out = eng.run(_requests(24))
    st = eng.stats()
    assert st["save_crashes"] == inj.save_crashes >= 1
    assert st["versions_promoted"] == 0
    assert {r.served_version for r in out if r.status == "SERVED"} == {0}
    assert any(p.suffix == ".tmp" for p in tmp_path.iterdir())
    # restart: torn tmp purged, seed checkpoint restored bit-exact
    eng2 = SNNServingEngine(_weights(5), PLAN, state_dir=tmp_path)
    assert eng2.store.serving.version == 0
    np.testing.assert_array_equal(np.asarray(eng2.weights),
                                  np.asarray(_weights()))
    assert not any(p.suffix == ".tmp" for p in tmp_path.iterdir())


class CorruptCanaryAfterPromotion:
    """Deterministic hook: once a refreshed version is serving, corrupt
    canary counts in-range (the range guard cannot see it) to force the
    post-promotion rollback path."""

    def __init__(self, engine_ref):
        self.engine_ref = engine_ref

    def __call__(self, ctx):
        if (ctx.get("kind") == "canary"
                and self.engine_ref[0]._pinned.origin == "refresh"):
            def corrupt(counts):
                out = np.array(counts)
                out[:, 0] += 1      # in-range drift: canary's job
                return out
            return corrupt
        return None


def test_canary_mismatch_on_refreshed_version_rolls_back(tmp_path):
    ref = []
    hook = CorruptCanaryAfterPromotion(ref)
    eng = SNNServingEngine(
        _weights(), PLAN, neuron_class=NEURON_CLASS,
        refresher=_refresher(), on_launch=hook, state_dir=tmp_path,
        policy=SNNServingPolicy(canary_every=1))
    ref.append(eng)
    eng.run(_requests(32))
    st = eng.stats()
    assert st["rollbacks"] >= 1
    assert st["canary_failures"] >= 1
    # the rolled-back version is demoted and its checkpoint deleted;
    # serving and a restarted process agree bit-exactly
    assert any(e["event"] == "rollback" for e in eng.refresh_events)
    assert eng.store.is_live(eng.store.serving.version)
    eng2 = SNNServingEngine(_weights(5), PLAN, state_dir=tmp_path)
    np.testing.assert_array_equal(np.asarray(eng2.weights),
                                  np.asarray(eng.weights))


def test_refresh_without_state_dir_is_memory_only():
    eng = SNNServingEngine(_weights(), PLAN, neuron_class=NEURON_CLASS,
                           refresher=_refresher())
    eng.run(_requests(16))
    assert eng.stats()["versions_promoted"] >= 1
    assert eng.store.ckpt is None           # nothing persisted anywhere


def test_frozen_serving_is_unchanged_without_refresher():
    """No refresher, no state_dir: the engine serves version 0 forever
    and the legacy counters/semantics are untouched."""
    eng = SNNServingEngine(_weights(), PLAN, neuron_class=NEURON_CLASS)
    out = eng.run(_requests(12))
    st = eng.stats()
    assert st["refresh_runs"] == 0
    assert st["weight_version"] == 0 and st["weight_origin"] == "seed"
    assert {r.served_version for r in out if r.status == "SERVED"} == {0}


# --- storm acceptance -------------------------------------------------------


def test_refresh_storm_acceptance(tmp_path):
    """The ISSUE's acceptance storm: launch faults + count corruption +
    candidate corruption + stalls + save crashes, all seeded.  Every
    request must reach a terminal status, every served response must be
    attributable to a version promoted and live at serve time, corrupt
    candidates must all die at the probe gate, and a post-storm restart
    must converge bit-exactly with the surviving serving bank."""
    inj = FaultInjector(FaultSpec(
        p_launch_error=0.15, p_corrupt=0.15, seed=11,
        p_refresh_corrupt=0.5, p_refresh_stall=0.25,
        refresh_stall_ms=1.0, p_save_crash=0.25))
    eng = SNNServingEngine(
        _weights(), PLAN, neuron_class=NEURON_CLASS,
        refresher=_refresher(), on_launch=inj, state_dir=tmp_path,
        keep_versions=64,
        policy=SNNServingPolicy(canary_every=3, reprobe_after=4))
    out = eng.run(_requests(48))
    st = eng.stats()
    assert all(r.terminal for r in out)
    assert st["version_violations"] == 0
    assert st["refresh_corrupt"] == inj.refresh_corruptions
    served = [r for r in out if r.status == "SERVED"]
    assert served
    for r in served:
        assert r.served_version in eng.store.promoted_order
        ver = eng.store.get(r.served_version)
        if ver is not None:
            np.testing.assert_array_equal(r.counts,
                                          _oracle(ver.weights, r))
    # storms replay bit-identically: same spec + traffic => identical
    # deterministic counters (timing/latency keys excluded)
    inj2 = FaultInjector(dataclasses.replace(inj.spec))
    eng2 = SNNServingEngine(
        _weights(), PLAN, neuron_class=NEURON_CLASS,
        refresher=_refresher(), on_launch=inj2,
        state_dir=tmp_path / "replay", keep_versions=64,
        policy=SNNServingPolicy(canary_every=3, reprobe_after=4))
    eng2.run(_requests(48))
    timing = {k for k in st if k.endswith(("_ms", "_rps"))
              or "_ms_" in k}
    st2 = eng2.stats()
    assert {k: v for k, v in st2.items() if k not in timing} \
        == {k: v for k, v in st.items() if k not in timing}
    # restart converges with the storm survivor
    eng3 = SNNServingEngine(_weights(5), PLAN, state_dir=tmp_path)
    np.testing.assert_array_equal(np.asarray(eng3.weights),
                                  np.asarray(eng.weights))
    assert eng3.store.serving.version == eng.store.serving.version


def test_fault_spec_validates_refresh_fields():
    with pytest.raises(ValueError, match="p_save_crash"):
        FaultSpec(p_save_crash=1.5)
    with pytest.raises(ValueError, match="refresh_stall_ms"):
        FaultSpec(refresh_stall_ms=-1.0)
