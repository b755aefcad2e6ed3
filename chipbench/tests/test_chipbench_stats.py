"""The arithmetic of the end-to-end metrics."""

import numpy as np
import pytest

from chipbench import stats


@pytest.mark.parametrize("q", [0, 5, 50, 90, 95, 99, 100])
def test_percentile_matches_numpy_linear(q):
    xs = list(np.random.default_rng(q).exponential(size=37))
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_of_small_samples():
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([1.0, 2.0], 50) == 1.5
    assert stats.percentile([4.0, 1.0, 3.0, 2.0], 100) == 4.0
    with pytest.raises(ValueError):
        stats.percentile([], 95)
    with pytest.raises(ValueError):
        stats.percentile([1.0], 101)


def test_mean_and_ratio():
    assert stats.mean([1, 2, 3, 6]) == 3.0
    assert stats.ratio(3, 12) == 0.25
    with pytest.raises(ValueError):
        stats.mean([])
    with pytest.raises(ValueError):
        stats.ratio(1, 0)
