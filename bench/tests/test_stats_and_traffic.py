"""Exact percentiles with misses, and the Poisson schedule's seeding."""

import math

import numpy as np
import pytest

from bench.harness import load, stats


def test_p99_is_exact_nearest_rank():
    xs = list(range(1, 201))  # 1..200
    assert stats.percentile(xs, 99) == 198
    assert stats.percentile(xs, 50) == 100
    assert stats.percentile([5.0], 99) == 5.0


def test_failures_count_as_misses_in_the_tail():
    ok = [1.0] * 98
    assert stats.percentile(ok + [math.inf] * 2, 99) == math.inf
    assert stats.percentile(ok + [2.0] + [math.inf], 99) == 2.0
    assert stats.percentile(ok + [math.inf] * 2, 50) == 1.0


def test_spread_is_the_interquartile_range_over_the_median():
    assert stats.spread([1, 2, 3, 4, 5]) == pytest.approx((4.5 - 1.5) / 3)


def test_poisson_schedule_is_determined_by_the_seed():
    a = load.fixed_count_schedule(1000, 2.0, np.random.default_rng(7))
    b = load.fixed_count_schedule(1000, 2.0, np.random.default_rng(7))
    c = load.fixed_count_schedule(1000, 2.0, np.random.default_rng(8))
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)


@pytest.mark.parametrize("seed", [0, 2**31 + 5, 3_000_000_001])
def test_every_seed_offers_the_same_count_in_the_window(seed):
    s = load.fixed_count_schedule(2500, 4.0, np.random.default_rng(seed))
    assert len(s) == 10_000
    assert np.all(np.diff(s) > 0) and 0 < s[0] and s[-1] < 4.0
    # gaps are exponential: mean 1/rate, coefficient of variation about 1
    gaps = np.diff(s)
    assert np.mean(gaps) == pytest.approx(1 / 2500, rel=0.05)
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(1.0, rel=0.1)


def test_the_copied_generator_matches_the_original_form():
    rng1, rng2 = np.random.default_rng(3), np.random.default_rng(3)
    np.testing.assert_allclose(load.poisson_arrivals(50, 100.0, rng1),
                               np.cumsum(rng2.exponential(0.01, 50)))
