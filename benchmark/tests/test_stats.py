"""Percentile and due-time arithmetic on fixed samples."""

import pytest

from lib import stats


def test_percentile_is_nearest_rank_and_a_measured_value():
    sample = [15, 20, 35, 40, 50]
    assert stats.percentile(sample, 5) == 15
    assert stats.percentile(sample, 30) == 20
    assert stats.percentile(sample, 40) == 20
    assert stats.percentile(sample, 50) == 35
    assert stats.percentile(sample, 100) == 50
    assert stats.percentile(list(range(1, 101)), 95) == 95
    assert stats.percentile([7.5], 95) == 7.5


def test_percentile_refuses_nonsense():
    with pytest.raises(ValueError):
        stats.percentile([], 95)
    with pytest.raises(ValueError):
        stats.percentile([1, 2], 0)


def test_latency_counts_from_the_due_time_not_the_send_time():
    # due at 10.100, sent 30 ms late, answered at 10.250: the user waited
    # 150 ms, and the generator's lag is its own number
    due, sent, done = 10.100, 10.130, 10.250
    assert stats.since_due_ms(due, done) == pytest.approx(150.0)
    assert stats.since_due_ms(due, sent) == pytest.approx(30.0)


def test_window_offsets_fill_the_window_whatever_the_order():
    gaps = [0.5, 1.5, 1.0, 2.0, 1.0]          # 4 arrivals + closing gap
    a = stats.window_offsets(gaps, 12.0)
    b = stats.window_offsets(list(reversed(gaps)), 12.0)
    assert a[0] == b[0] == 0.0
    assert len(a) == len(b) == 5
    assert a == pytest.approx([0.0, 1.0, 4.0, 6.0, 10.0])
    assert all(0.0 <= t < 12.0 for t in a + b)
    # the same multiset of gaps, in another order
    def diffs(xs):
        return sorted(round(y - x, 9) for x, y in zip(xs, xs[1:] + [12.0]))
    assert diffs(a) == diffs(b)


def test_spread_is_the_interquartile_share_of_the_median():
    values = [100, 102, 98, 101, 99, 100]
    assert stats.spread(values) == pytest.approx(
        (101.25 - 98.75) / 100.0)
