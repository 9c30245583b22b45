"""The comparison that decides ``correct``, on the CPU at test sizes.

Each test skips the harness's look for a chip and drives the rest of a
run through the program's normal entry (the platform's ``ref`` kernel
path here): a sound program comes out correct; the control (the
reference computed from 4-bit instead of 8-bit intensities, put in the
program's place) and every fault the cells can have come out not
correct, in the result line the harness prints.
"""

from __future__ import annotations

import copy
import json
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import io_callback

from chip import control, serve, train, traffic
from chip import run as bench

HERE = Path(__file__).resolve().parent
PEAK = json.loads((HERE / "peaks.json").read_text())["TPU v5 lite"]
W22A = json.loads((HERE / "configs/w22a-784x40.json").read_text())
SEED = 2**33 + 17


def _serve_mix(name: str) -> dict:
    mix = traffic.load_mix(name)
    mix.update(rate_per_s=300, pool=16)
    return mix


def _train_mix() -> dict:
    mix = traffic.load_mix("train-parallel")
    mix.update(chunk=16, pool=16, pool_chunks=4)
    return mix


def _serve(mix, prog=None):
    return serve.run(W22A, mix, SEED, 0.4, chips=1, peak=PEAK,
                     t_start=0.0, prog=prog)


def _train(prog=None):
    return train.run(W22A, _train_mix(), SEED, 0.2, chips=1, peak=PEAK,
                     t_start=0.0, prog=prog)


def _correct(rec) -> bool:
    return all(v <= limit for v, limit in rec.checks.values())


def _serving_program(fault: str):
    prog = serve.program()
    base = prog.SNNServingEngine

    class Faulty(base):
        def _launch_counts(self, batch, t_pad, level, **kw):
            if fault == "half":          # half of each batch never served
                kept = batch[:max(1, len(batch) // 2)]
                batch[:] = kept
            counts = super()._launch_counts(batch, t_pad, level, **kw)
            if fault == "answer":        # one neuron's count altered
                counts = np.array(counts)
                counts[:, 0] ^= 1
            return counts

    return types.SimpleNamespace(**{**vars(prog),
                                    "SNNServingEngine": Faulty})


def _line(name: str, rec) -> dict:
    plan = bench.cell_plan(bench.load_spec(), name)
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    return bench.result_line(plan, rec, device, False)


def test_sound_serving_is_correct_and_the_control_is_not():
    rec = _serve(_serve_mix("serve-poisson"))
    assert _correct(rec), rec.checks
    assert rec.attempted == 120 and rec.failed == 0
    control.as_control(rec)
    line = _line("w22a.serve-poisson", rec)
    assert line["correct"] is False
    assert line["checks"]["count_mismatch"]["value"] > 0


def _offline():
    cfg = dict(W22A, max_batch=8)
    mix = traffic.load_mix("serve-offline")
    mix.update(pool=16, max_per_s=2000)
    return serve.run(cfg, mix, SEED, 0.3, chips=1, peak=PEAK, t_start=0.0)


def test_offline_serving_is_correct():
    rec = _offline()
    assert _correct(rec), rec.checks
    assert rec.completed > 0


def test_offline_control_is_not_correct():
    rec = _offline()
    control.as_control(rec)
    line = _line("ens6400.serve-offline", rec)
    assert line["correct"] is False
    assert line["checks"]["count_mismatch"]["value"] > 0


@pytest.mark.parametrize("fault", ["answer", "half"])
def test_a_serving_fault_is_not_correct(fault):
    rec = _serve(_serve_mix("serve-poisson"), _serving_program(fault))
    assert not _correct(rec), rec.checks


def test_sound_training_is_correct_and_the_control_is_not():
    rec = _train()
    assert _correct(rec), rec.checks
    control.as_control(rec)
    line = _line("w22a.train-parallel", rec)
    assert line["correct"] is False
    assert line["checks"]["weight_words_differ"]["value"] > 0


@pytest.mark.parametrize("calls", [77, 3, 1])
def test_the_window_calls_compared_are_the_last_and_seeded_others(calls):
    picks = train.window_picks(SEED, calls, 3)
    assert len(picks) == min(3, calls) and picks[-1] == calls - 1
    assert picks == sorted(set(picks))
    assert all(0 <= j < calls - 1 for j in picks[:-1])
    assert picks == train.window_picks(SEED, calls, 3)


def _training_program(fault: str, after: int = 0):
    """The program whose training call has ``fault`` from its call
    ``after`` on (0: every call)."""
    prog = train.program()
    real = prog.train_stream_batch
    calls = [0]

    def unchanged(engine, rfs, *a, intensities=None, **kw):
        b, n = rfs.v.shape
        return rfs, np.zeros((b, intensities.shape[1], n), np.int32)

    def half(engine, rfs, *a, teach=None, intensities=None, seeds=None,
             **kw):
        k = intensities.shape[1] // 2
        return real(engine, rfs, teach=teach[:, :k],
                    intensities=intensities[:, :k], seeds=seeds[:k], **kw)

    faulty = {"unchanged": unchanged, "half": half}[fault]

    def tick():
        calls[0] += 1
        return np.int32(calls[0])

    def fn(engine, rfs, *a, **kw):
        # the call's number, counted when it runs (the step is jitted)
        k = io_callback(tick, jax.ShapeDtypeStruct((), jnp.int32),
                        ordered=True)
        good, raster = real(engine, rfs, *a, **kw)
        bad, _ = faulty(engine, rfs, *a, **kw)
        return jax.tree.map(lambda g, b: jnp.where(k > after, b, g),
                            good, bad), raster

    return types.SimpleNamespace(**{**vars(prog), "train_stream_batch": fn})


@pytest.mark.parametrize("fault", ["unchanged", "half"])
def test_a_training_fault_is_not_correct(fault):
    rec = _train(_training_program(fault))
    assert not _correct(rec), rec.checks


@pytest.mark.parametrize("fault", ["unchanged", "half"])
def test_a_training_fault_inside_the_window_is_not_correct(fault):
    # sound through set-up's calls, faulty in the measured window only
    mix = _train_mix()
    rec = _train(_training_program(fault, after=mix["check_steps"]))
    assert not _correct(rec), rec.checks


def test_the_result_line_holds_the_checks_last():
    spec = bench.load_spec()
    plan = bench.cell_plan(spec, "w22a.serve-poisson")
    plan = copy.deepcopy(plan)
    plan["mix"].update(rate_per_s=300, pool=16)
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    rec = bench.execute(plan, SEED, 0.3, False, device, PEAK)
    line = bench.result_line(plan, rec, device, False)
    assert list(line)[-1] == "checks"
    assert line["correct"] is True
    assert set(line["metrics"]) == {"e2e_p50_ms", "setup_s"}
    assert line["checks"]["count_mismatch"] == {"value": 0, "limit": 0}
