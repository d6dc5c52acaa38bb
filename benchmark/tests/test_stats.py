"""Percentile and rate arithmetic."""

import pytest

from benchmark import stats


def test_percentile_interpolates_like_numpy():
    np = pytest.importorskip("numpy")
    vals = [3.0, 1.0, 4.0, 1.5, 9.0, 2.6, 5.0]
    for q in (0, 50, 90, 95, 100):
        assert stats.percentile(vals, q) == pytest.approx(
            float(np.percentile(vals, q)))
    assert stats.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_rate_is_all_the_work_over_all_the_time():
    assert stats.rate_per_s(900, 10.0, 40.0) == 30.0
    with pytest.raises(ValueError):
        stats.rate_per_s(1, 5.0, 5.0)


def test_tokens_and_gaps_count_where_the_later_stamp_falls():
    stamps = [0.9, 1.0, 1.1, 1.3, 2.1]
    assert stats.in_window(stamps, 1.0, 2.0) == 3
    gaps = stats.gaps_ms(stamps, 1.0, 2.0)
    assert gaps == pytest.approx([100.0, 100.0, 200.0])
    assert stats.gaps_ms([1.5], 1.0, 2.0) == []


def test_iqr_share_is_the_contracts_spread():
    import statistics
    vals = [100, 101, 102, 103, 104, 110]
    q1, q2, q3 = statistics.quantiles(vals, n=4)
    assert stats.iqr_share(vals) == pytest.approx((q3 - q1) / q2)


def test_trimmed_range_leaves_out_one_far_run():
    vals = [640.0, 641.0, 642.0, 643.0, 644.0, 610.0]
    # the median of the whole set; 610 is farthest from it and goes
    assert stats.trimmed_range_share(vals) == pytest.approx(4.0 / 641.5)
    # two far runs: one goes, the other still widens the range
    two = [640.0, 641.0, 642.0, 643.0, 610.0, 612.0]
    assert stats.trimmed_range_share(two) == pytest.approx(31.0 / 640.5)


def test_trimmed_range_where_dropping_narrows_nothing():
    # both ends are held twice: the range is the same with one run less
    assert stats.trimmed_range_share([10.0, 10.0, 11.0, 12.0, 12.0]) \
        == pytest.approx(2.0 / 11.0)
    assert stats.trimmed_range_share([5.0, 5.0, 5.0]) == 0.0


def test_trimmed_range_wants_three_runs():
    with pytest.raises(ValueError):
        stats.trimmed_range_share([1.0, 2.0])


def _two_streams(hole_at=None, end=13.1):
    """Two streams that each get a token every 20 ms, 1 ms apart (one
    iteration serves both), from 9.9 s to `end`; `hole_at`: no token
    for the 2 s after it."""
    a, b, t = [], [], 9.9
    while t < end:
        if hole_at is None or not hole_at < t < hole_at + 2.0:
            a.append(t)
            b.append(t + 0.001)
        t = round(t + 0.02, 6)
    return [a, b]


def test_no_token_gaps_merge_the_streams_and_keep_to_the_window():
    gaps = stats.no_token_gaps(_two_streams(), 10.0, 13.0)
    assert gaps[0][0] == 10.0 and gaps[-1][1] == 13.0
    assert all(10.0 <= a < b <= 13.0 for a, b in gaps)
    assert sum(b - a for a, b in gaps) == pytest.approx(3.0)
    # stamps outside the window make no interval; inside, an iteration
    # is one interval of 1 ms (its two deliveries) and one of 19 ms
    lengths = sorted(round((b - a) * 1e3) for a, b in gaps)
    assert set(lengths) <= {1, 19, 20} and lengths.count(19) >= 148
    # the median instant lies in an ordinary iteration's interval, not
    # in one of the many 1 ms ones
    assert stats.weighted_median_s(gaps) == pytest.approx(0.019, abs=1e-6)
    assert stats.stall_share(gaps, 10.0, 13.0) == 0.0
    with pytest.raises(ValueError):
        stats.no_token_gaps([[1.0]], 2.0, 2.0)


def test_a_two_second_hole_is_a_stall():
    gaps = stats.no_token_gaps(_two_streams(10.5, 20.1), 10.0, 20.0)
    longest = max(b - a for a, b in gaps)
    assert longest == pytest.approx(1.999, abs=1e-6)
    assert stats.weighted_median_s(gaps) == pytest.approx(0.019, abs=1e-6)
    (hole,) = stats.stalls(gaps)
    assert hole[0] == pytest.approx(10.501) and hole[1] == 12.5
    assert stats.stall_share(gaps, 10.0, 20.0) == pytest.approx(
        longest / 10.0)
    # a window with no token at all is one interval, all of it usual
    assert stats.no_token_gaps([[1.0], []], 2.0, 3.0) == [(2.0, 3.0)]
    assert stats.stall_share([(2.0, 3.0)], 2.0, 3.0) == 0.0


def test_by_tenth_sums_weights_inside_the_window():
    events = [(0.5, 7), (1.0, 1), (1.05, 2), (1.95, 3), (2.0, 4), (2.5, 9)]
    assert stats.by_tenth(events, 1.0, 2.0) == [3, 0, 0, 0, 0, 0, 0, 0, 0, 7]
    with pytest.raises(ValueError):
        stats.by_tenth(events, 1.0, 1.0)
