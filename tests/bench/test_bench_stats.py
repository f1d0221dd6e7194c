"""The score of a run: total over total and the median of passes beside
it, nearest-rank percentiles and their sample-count guard, the
slow-pass share."""

import pytest

from benchlib import stats


def test_the_end_to_end_rate_carries_a_stall_that_the_median_of_passes_sheds():
    # nine passes of 8000 tx in 1.0 s and one stalled pass of 3.0 s
    walls = [1.0] * 9 + [3.0]
    work = [8000] * 10
    assert stats.total_rate(work, walls) == pytest.approx(80000 / 12.0)
    assert stats.total_rate(work, walls) < 0.85 * 8000.0
    assert stats.median_rate(work, walls) == 8000.0   # per-layer: a typical pass


@pytest.mark.parametrize("rate", [stats.total_rate, stats.median_rate])
@pytest.mark.parametrize("work,walls", [([1, 2], [1.0]), ([1], [0.0]), ([], [])])
def test_a_rate_refuses_mismatched_or_empty_input(rate, work, walls):
    with pytest.raises(ValueError):
        rate(work, walls)


@pytest.mark.parametrize("p,want", [(50, 50.0), (90, 90.0), (95, 95.0), (100, 100.0), (1, 1.0)])
def test_percentile_is_nearest_rank_a_value_that_occurred(p, want):
    assert stats.percentile(range(1, 101), p) == want


def test_percentile_of_few_samples_is_the_maximum():
    assert stats.percentile([5.0, 1.0, 3.0], 95) == 5.0


@pytest.mark.parametrize("p,n", [(95, 200), (90, 100), (99, 1000), (50, 20)])
def test_a_percentile_needs_ten_samples_beyond_it(p, n):
    assert stats.needed_samples(p) == n
    assert stats.percentile_supported(n, p)
    assert not stats.percentile_supported(n - 1, p)
    assert stats.samples_beyond(n, p) == stats.MIN_BEYOND


def test_slow_share_counts_passes_over_a_quarter_above_the_median():
    walls = [1.0] * 18 + [1.2, 1.3]
    assert stats.slow_share(walls) == pytest.approx(5.0)
    assert stats.slow_share([1.0] * 10) == 0.0
