"""The traffic generator gives every seed the same work."""

import numpy as np

from chipbench import traffic


def test_poisson_schedule_same_gaps_every_seed():
    a = traffic.poisson_schedule(60.0, 20.0, np.random.default_rng(1))
    b = traffic.poisson_schedule(60.0, 20.0, np.random.default_rng(2))
    assert len(a) == len(b) == 1200
    assert a[0] == 0.0 and np.all(np.diff(a) > 0)
    assert not np.array_equal(a, b)
    ga = np.sort(np.diff(np.append(a, 20.0)))
    gb = np.sort(np.diff(np.append(b, 20.0)))
    np.testing.assert_allclose(ga, gb, rtol=1e-9, atol=1e-12)
    # exponential gaps: the mean is 1/rate, the median ln 2 / rate
    assert abs(np.mean(ga) - 20.0 / 1200) < 1e-12
    assert abs(np.median(ga) * 60.0 - np.log(2)) < 0.01


def test_group_sequence_exact_shares():
    groups = [{"share": 0.5}, {"share": 0.5}]
    seq = traffic.group_sequence(groups, 1001, np.random.default_rng(5))
    counts = np.bincount(seq)
    assert counts.tolist() in ([501, 500], [500, 501])
    seq2 = traffic.group_sequence(groups, 1001, np.random.default_rng(6))
    assert sorted(seq2.tolist()) == sorted(seq.tolist())
