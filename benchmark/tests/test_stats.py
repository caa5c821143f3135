import pytest

from benchmark.harness import stats


def test_percentile_interpolates_like_numpy():
    xs = [10, 20, 30, 40, 50]
    assert stats.percentile(xs, 50) == 30
    assert stats.percentile(xs, 95) == pytest.approx(48.0)
    assert stats.percentile(xs, 0) == 10 and stats.percentile(xs, 100) == 50
    assert stats.percentile([7], 95) == 7
    assert stats.percentile([], 95) is None


def test_failed_request_counts_as_the_worst_sample():
    xs = [1.0] * 19
    assert stats.percentile(stats.with_failures(xs, 0), 95) == 1.0
    # one failure in twenty moves the p95 towards the worst sample
    worst = stats.percentile(stats.with_failures(xs, 1, worst=1000.0), 95)
    assert worst > 1.0
    assert stats.percentile(stats.with_failures(xs, 3, worst=1000.0), 95) \
        == 1000.0
    # without a stated wait, the largest sample seen stands in
    assert max(stats.with_failures([1.0, 5.0], 2)) == 5.0

