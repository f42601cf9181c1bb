import pytest

from benchmarks import stats


@pytest.mark.parametrize("n, asked, want", [
    (1564, 99.0, 99.0),   # 15 samples beyond p99
    (999, 99.0, 95.0),    # 9.99 beyond p99: falls to p95
    (780, 99.0, 95.0),
    (150, 99.0, 90.0),
    (80, 99.0, 75.0),
    (30, 99.0, 50.0),
    (100000, 99.0, 99.0),  # never above what was asked
    (100000, 99.9, 99.9),
])
def test_supported_percentile(n, asked, want):
    assert stats.supported_percentile(n, asked) == want


def test_tail_is_a_measured_value_and_counts_samples():
    xs = list(range(1, 2001))
    value, pct, n = stats.tail(xs, 99.0)
    assert (value, pct, n) == (1980.0, 99.0, 2000)
    assert stats.median([5, 1, 3]) == 3.0
    assert stats.nearest_rank([1, 2, 3, 4], 50.0) == 2


def test_no_samples_is_an_error():
    with pytest.raises(ValueError):
        stats.median([])
