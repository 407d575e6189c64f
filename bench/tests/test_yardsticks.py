"""The benchmark's fixed yardsticks: work tables, peaks, arrivals."""

import math

import pytest

from bench import common
from bench.peaks import peaks
from bench.traffic import gamma_arrivals
from bench.work.layers import totals


def test_enet512_work_is_todays_total():
    layers = common.load_module(common.BENCH / "work" / "enet-512.py").layers()
    t = totals(layers, 1)
    assert len(layers) == 89
    assert t["flops"] / 1e9 == pytest.approx(4.121, abs=5e-4)
    share = {k: totals(layers, 1, kinds=(k,))["flops"] / t["flops"]
             for k in ("dense", "dilated", "transposed")}
    assert share["dense"] == pytest.approx(0.760, abs=1e-3)
    assert share["dilated"] == pytest.approx(0.147, abs=1e-3)
    assert share["transposed"] == pytest.approx(0.093, abs=1e-3)
    assert totals(layers, 10)["flops"] == pytest.approx(10 * t["flops"])


def test_dcgan64_work_is_todays_total():
    layers = common.load_module(common.BENCH / "work" / "dcgan-64.py").layers()
    t = totals(layers, 1)
    assert t["flops"] / 1e9 == pytest.approx(0.209, abs=5e-4)
    tr = totals(layers, 1, kinds=("transposed",))["flops"]
    assert tr / t["flops"] == pytest.approx(0.992, abs=1e-3)


def test_least_time_is_the_larger_bound():
    layers = common.load_module(common.BENCH / "work" / "dcgan-64.py").layers()
    p = peaks("TPU v5 lite")
    for l in layers:
        assert l.min_seconds(64, p) == max(l.flops(64) / p["bf16_flops"],
                                           l.bytes(64) / p["hbm_bytes_per_s"])


def test_unknown_device_is_an_error():
    assert peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks("cpu")


def test_same_seed_same_schedule():
    a = gamma_arrivals.schedule(3000.0, 2.0, 2.0, seed=2**31 + 7)
    b = gamma_arrivals.schedule(3000.0, 2.0, 2.0, seed=2**31 + 7)
    c = gamma_arrivals.schedule(3000.0, 2.0, 2.0, seed=11)
    assert a == b and a != c
    assert len(a) == len(c) == 6000
    assert sorted(a) == a and a[0] == 0.0 and a[-1] < 2.0


def test_seeds_rotate_one_gap_sequence():
    import numpy as np

    a = np.diff(gamma_arrivals.schedule(3000.0, 2.0, 2.0, seed=1) + [2.0])
    b = np.diff(gamma_arrivals.schedule(3000.0, 2.0, 2.0, seed=2) + [2.0])
    assert not np.allclose(a, b)
    shifts = [k for k in range(len(a)) if np.allclose(np.roll(a, k), b)]
    assert len(shifts) == 1


def test_measured_cv_is_near_two():
    t = gamma_arrivals.schedule(1500.0, 2.0, 4.0, seed=3)
    assert gamma_arrivals.measured_cv(t) == pytest.approx(2.0, rel=0.1)


def test_percentile_counts_failures_as_missing():
    assert common.percentile([1.0] * 95 + [math.inf] * 5, 95) == 1.0
    assert common.percentile([1.0] * 94 + [math.inf] * 6, 95) == math.inf


def test_seed_words_take_any_whole_number():
    assert common.seed_words(2**40 + 3) == common.seed_words(2**40 + 3)
    assert common.seed_words(-1) != common.seed_words(1)
