"""End-to-end metric arithmetic: the rate over the whole window and the
percentile of every gap."""
import numpy as np
import pytest

import bench_helpers  # noqa: F401  (puts the repository on the path)
from bench.lib import stats


def test_rate_is_all_windows_over_all_seconds():
    assert stats.rate(120, 10.0, 40.0) == pytest.approx(4.0)


def test_gaps_are_per_stream_and_inside_the_window():
    answers = [("a", 1.0), ("a", 3.0), ("a", 4.5), ("b", 2.0), ("b", 2.5),
               ("a", 0.5), ("b", 9.0)]
    gaps = stats.answer_gaps(answers, t0=0.9, t1=5.0)
    # a: 1.0 -> 3.0 -> 4.5; b: 2.0 -> 2.5; 0.5 and 9.0 lie outside
    assert sorted(gaps) == pytest.approx([0.5, 1.5, 2.0])


def test_percentile_of_all_gaps_not_a_median_of_chunks():
    rng = np.random.default_rng(0)
    gaps = list(rng.exponential(1.0, size=400))
    chunks = [stats.percentile(gaps[i:i + 100], 90) for i in range(0, 400, 100)]
    whole = stats.percentile(gaps, 90)
    assert whole == pytest.approx(float(np.percentile(gaps, 90)))
    assert whole != pytest.approx(float(np.median(chunks)))


def test_spread_is_interquartile_over_median():
    assert stats.spread([1.0, 2.0, 3.0, 4.0, 5.0, 6.0]) == pytest.approx(
        (5.25 - 1.75) / 3.5)


def test_percentile_needs_samples():
    with pytest.raises(ValueError):
        stats.percentile([], 90)
