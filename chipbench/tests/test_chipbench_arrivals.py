"""The open-loop generator: reproducible from the seed, scheduled in seconds, fixed work."""
import numpy as np
import pytest

from chipbench_testing import BENCH  # noqa: F401

import arrivals


def test_same_seed_same_schedule_other_seed_other_order():
    a = arrivals.poisson_schedule(2**33 + 1, 16, 500.0, 10.0)
    b = arrivals.poisson_schedule(2**33 + 1, 16, 500.0, 10.0)
    c = arrivals.poisson_schedule(7, 16, 500.0, 10.0)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert len(c[0]) == len(a[0])
    assert not np.array_equal(a[0], c[0])


def test_due_times_are_seconds_inside_the_window_and_sorted():
    due, stream, index = arrivals.poisson_schedule(3, 16, 1600.0, 5.0)
    assert len(due) == 8000
    assert (due >= 0).all() and (due < 5.0).all()
    assert (np.diff(due) >= 0).all()
    assert np.bincount(stream).tolist() == [500] * 16
    for s in range(16):
        assert (np.diff(due[stream == s]) >= 0).all()
        assert sorted(index[stream == s].tolist()) == list(range((stream == s).sum()))


def test_rejects_non_positive_rate():
    with pytest.raises(ValueError):
        arrivals.poisson_schedule(0, 4, 0.0, 1.0)
