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
