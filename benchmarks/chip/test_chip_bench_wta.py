"""The unsupervised cell's comparison on the CPU at test sizes: its copy
of the reference agrees with the program's own reference, the loop's
sound run is correct, and the control (the reference without
inhibition) is not."""

from __future__ import annotations

import json
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from chip import control, reference_wta, traffic, train_wta
from chip import run as bench
from chip.metrics import spikes_per_sample, train_kernel_ms

HERE = Path(__file__).resolve().parent
PEAK = json.loads((HERE / "peaks.json").read_text())["TPU v5 lite"]
DC = json.loads((HERE / "configs/dc-784x6400-wta.json").read_text())
SEED = 2**33 + 29
SMALL = dict(DC, n_neurons=40, threshold=8, w_inh=8)


def _mix() -> dict:
    mix = traffic.load_mix("train-unsup-t350")
    mix.update(t_steps=12, chunk=4, pool=16, pool_chunks=3)
    return mix


def _run():
    return train_wta.run(SMALL, _mix(), SEED, 0.2, chips=1, peak=PEAK,
                         t_start=0.0)


def _correct(rec) -> bool:
    return all(v <= limit for v, limit in rec.checks.values())


@pytest.mark.parametrize("w_inh", [8, 0])
def test_reference_copy_agrees_with_the_programs_reference(w_inh):
    import repro.core  # noqa: F401  (initialises repro.engine's imports)
    from repro.core.rvsnn import SnnRegFile
    from repro.engine import SNNEngine, SNNEnginePlan
    from repro.engine.engine import train_stream_batch

    chunks = train_wta._chunks(_mix(), SEED)
    x, sd = chunks[0]
    w0, lf0 = train_wta.initial_state(SMALL, SEED)
    th0 = jnp.arange(40, dtype=jnp.int32) % 3
    plan = SNNEnginePlan(threshold=8, leak=0, w_exp=78, gain=4, n_syn=784,
                         ltp_prob=16, encode="kernel", kernel_backend="ref",
                         w_inh=w_inh, theta_plus=1)
    rfs = SnnRegFile(spike=jnp.zeros((1, 25), jnp.uint32),
                     v=jnp.zeros((1, 40), jnp.int32), lfsr=lf0, weights=w0,
                     theta=th0[None])
    got, counts = train_stream_batch(
        SNNEngine(plan), rfs, intensities=jnp.asarray(x)[None],
        seeds=jnp.asarray(sd.astype(np.int32)), n_steps=12)
    assert int(counts.sum()) > 0
    kw = dict(t_steps=12, threshold=8, leak=0, w_exp=78, gain=4,
              n_syn=784, ltp_prob=16, theta_plus=1)
    want = reference_wta.train_stream(w0[0], lf0[0], th0, x, sd,
                                      w_inh=w_inh, **kw)
    for g, w in zip((got.weights[0], got.lfsr[0], got.theta[0]), want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    other = reference_wta.train_stream(w0[0], lf0[0], th0, x, sd,
                                       w_inh=8 - w_inh, **kw)
    assert not np.array_equal(np.asarray(other[2]), np.asarray(want[2]))


def test_sound_run_is_correct_and_the_control_is_not():
    rec = _run()
    assert _correct(rec), rec.checks
    assert set(rec.checks) == {"weight_words_differ", "lfsr_words_differ",
                               "theta_words_differ"}
    assert rec.completed == rec.launches == 4 * rec.attempted
    assert spikes_per_sample.read(rec) == rec.extra["spikes"] / rec.completed
    assert train_kernel_ms.read(rec) is None     # no trace
    control.as_control(rec)
    plan = bench.cell_plan(bench.load_spec(), "dcwta6400.train-unsup")
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    line = bench.result_line(plan, rec, device, False)
    assert line["correct"] is False
    assert line["checks"]["theta_words_differ"]["value"] > 0
    assert set(line["metrics"]) == {"presentations_per_s", "setup_s"}
