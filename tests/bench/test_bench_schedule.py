"""Seeded traffic repeats exactly and gives each seed the same work."""
import numpy as np
import pytest

from benchmarks.chip import schedule

BIG = 2 ** 31 + 12345


@pytest.mark.parametrize("seed", [0, 7, BIG, -3, 2 ** 70])
def test_open_loop_repeats_exactly(seed):
    a = schedule.open_loop(2000.0, 3.0, 3, schedule.rng(seed))
    b = schedule.open_loop(2000.0, 3.0, 3, schedule.rng(seed))
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])


def test_each_seed_gets_the_same_work_in_another_order():
    a_off, a_kind = schedule.open_loop(2000.0, 3.0, 3, schedule.rng(1))
    b_off, b_kind = schedule.open_loop(2000.0, 3.0, 3, schedule.rng(2))
    assert len(a_off) == len(b_off) == 6000
    assert np.bincount(a_kind).tolist() == [2000, 2000, 2000]
    assert np.bincount(b_kind).tolist() == [2000, 2000, 2000]
    assert not np.array_equal(a_off, b_off)
    assert not np.array_equal(a_kind, b_kind)
    assert np.all(np.diff(a_off) >= 0)
    assert 0 <= a_off[0] and a_off[-1] < 3.0


def test_arrivals_look_poisson():
    off, _ = schedule.open_loop(4000.0, 5.0, 1, schedule.rng(BIG))
    gaps = np.diff(off)
    assert np.mean(gaps) == pytest.approx(1 / 4000, rel=0.05)
    # exponential gaps: the standard deviation equals the mean
    assert np.std(gaps) == pytest.approx(np.mean(gaps), rel=0.05)


def test_seu_descriptors_stay_in_range():
    d = schedule.seu_descriptors(500, tiles=256, bs=256, n=1024,
                                 eps=(50.0, 300.0), gen=schedule.rng(BIG))
    assert d.shape == (500, 6) and d.dtype == np.float32
    assert d[:, 0].max() < 256 and d[:, 1].max() < 256
    assert d[:, 2].max() < 1024 and np.all(d[:, 3] == 1)
    mag = np.hypot(d[:, 4], d[:, 5])
    assert mag.min() >= 50.0 - 1e-3 and mag.max() <= 300.0 + 1e-3


def test_streams_differ():
    a = schedule.rng(5, 0).integers(0, 1 << 30, 8)
    b = schedule.rng(5, 1).integers(0, 1 << 30, 8)
    assert not np.array_equal(a, b)
