"""The exact percentile arithmetic of the latency metrics."""

import numpy as np
import pytest

from chipbench import bench


@pytest.mark.parametrize("values,p,want", [
    (list(range(1, 101)), 50, 50.5),
    (list(range(1, 101)), 99, 99.01),
    (list(range(1, 101)), 100, 100.0),
    (list(range(1, 101)), 0, 1.0),
    ([7.0], 99, 7.0),
    ([3.0, 1.0, 2.0], 50, 2.0),
    ([10.0, 20.0], 25, 12.5),
])
def test_percentile_on_known_samples(values, p, want):
    assert bench.percentile(values, p) == pytest.approx(want, abs=1e-12)


def test_percentile_matches_numpy_default():
    v = np.random.default_rng(3).exponential(size=1001)
    for p in (1, 50, 95, 99, 99.9):
        assert bench.percentile(v, p) == pytest.approx(
            float(np.percentile(v, p)), rel=1e-12)


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        bench.percentile([], 50)
