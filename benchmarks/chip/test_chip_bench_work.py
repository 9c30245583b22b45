"""Ops and bytes of each kernel at each cell's shapes against hand counts,
the peaks table, and the seeded traffic generator."""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from chip import arrivals, traffic
from chip.work import infer_encode, roofline, train_encode

HERE = Path(__file__).resolve().parent
W22A = json.loads((HERE / "configs/w22a-784x40.json").read_text())
ENS = json.loads((HERE / "configs/ens-784x6400.json").read_text())
PEAKS = json.loads((HERE / "peaks.json").read_text())


def test_infer_encode_at_the_serve_cell_shapes():
    # 32 requests of T=72 on 784 x 40: 2 ops per synapse per cycle
    ops, nbytes = infer_encode.work(W22A, [72] * 32)
    assert ops == 2 * 784 * 40 * 72 * 32 == 144_506_880
    # weights 40 rows x 25 words x 4 B; per request 784 B + seed + length;
    # counts 40 x 4 B per request
    assert nbytes == 40 * 25 * 4 + 32 * (784 + 8) + 32 * 160 == 34_464


def test_infer_encode_at_the_offline_cell_shapes():
    ops, nbytes = infer_encode.work(ENS, [72] * 256)
    assert ops == 2 * 784 * 6400 * 72 * 256 == 184_968_806_400
    assert nbytes == 6400 * 100 + 256 * 792 + 256 * 6400 * 4 == 7_396_352


def test_train_encode_at_the_train_cell_shapes():
    # one launch: 4 blocks of 10 neurons, one sample of T=72 each;
    # 2 forward + 2 STDP ops per synapse per cycle
    ops, nbytes = train_encode.work(W22A, [72] * 4)
    assert ops == 4 * 4 * 784 * 10 * 72 == 9_031_680
    state = 4 * 4 * 10 * 25 * 4          # weights + LFSR in and out
    inputs = 4 * (784 + 8 + 80)          # intensities, seed, ltp, teach, v
    outputs = 4 * (72 * 10 + 40)         # raster + v
    assert nbytes == state + inputs + outputs == 22_528


@pytest.mark.parametrize("work", [infer_encode, train_encode])
def test_padded_slots_and_lanes_do_not_count(work):
    cfg = W22A
    assert work.work(cfg, [])[0] == 0
    one = work.work(cfg, [72])
    # a batch padded to 32 slots with 1 real request counts 1 request
    assert work.work(cfg, [72]) == one
    # 784 inputs count as 784 (not the 4,096 of 128 lanes x 32 bits)
    assert one[0] % 784 == 0 and one[0] % 4096 != 0


def test_roofline_names_its_bound():
    peak = PEAKS["TPU v5 lite"]
    share, bound = roofline(393e12, 0.0, 2.0, peak)
    assert bound == "ops" and share == pytest.approx(50.0)
    share, bound = roofline(1.0, 819e9, 4.0, peak)
    assert bound == "bytes" and share == pytest.approx(25.0)
    assert roofline(0.0, 10.0, 1.0, peak) is None


def test_unknown_device_kind_is_an_error(monkeypatch):
    import jax

    from chip import run as bench

    class Dev:
        platform, device_kind = "tpu", "TPU v99"

    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    with pytest.raises(KeyError, match="TPU v99"):
        bench.device_info(1)


def test_a_cpu_host_is_not_a_chip():
    from chip import run as bench

    with pytest.raises(bench.NoChip):
        bench.device_info(1)


def test_same_seed_same_arrivals_and_payloads():
    big = 2**31 + 12345
    a = traffic.arrival_times({"arrivals": "poisson", "rate_per_s": 5000},
                              big, 2.0)
    b = traffic.arrival_times({"arrivals": "poisson", "rate_per_s": 5000},
                              big, 2.0)
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(traffic.counter_seeds(big, 1, 100),
                                  traffic.counter_seeds(big, 1, 100))
    x1, y1 = traffic.digit_pool(big, 8)
    x2, y2 = traffic.digit_pool(big, 8)
    np.testing.assert_array_equal(x1, x2)
    np.testing.assert_array_equal(y1, y2)
    # mostly dark pixels (under 3% spike probability), like MNIST
    assert (x1 < 8).mean() > 0.5


def test_every_seed_offers_the_same_work_in_another_order():
    mix = {"arrivals": "poisson", "rate_per_s": 3000}
    a = traffic.arrival_times(mix, 1, 1.0)
    b = traffic.arrival_times(mix, 2**40 + 7, 1.0)
    assert len(a) == len(b) == 3000
    assert a[0] == b[0] == 0.0 and a[-1] < 1.0 and b[-1] < 1.0
    # the same gaps (the last one runs to the window's end), reordered
    np.testing.assert_allclose(np.sort(np.diff(a, append=1.0)),
                               np.sort(np.diff(b, append=1.0)),
                               rtol=1e-6, atol=1e-12)
    assert not np.array_equal(a, b)
    assert abs(np.diff(a).mean() * 3000 - 1.0) < 1e-3


def test_u64_is_the_loadgen_hash():
    from repro.loadgen.arrivals import u64

    for seed in (0, 7, 2**31 + 5, 2**62 + 3):
        assert arrivals.u64(seed, 1, 2) == u64(seed, 1, 2)
