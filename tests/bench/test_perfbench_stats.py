"""Percentiles and the drained rate of the benchmark."""

import numpy as np
import pytest

import _perfbench_helpers  # noqa: F401  (puts the repo root on the path)
from bench.stats import drained_rate, percentile


@pytest.mark.parametrize("q", [0, 5, 50, 95, 99, 100])
@pytest.mark.parametrize("n", [1, 2, 7, 100, 1001])
def test_percentile_matches_numpy_linear(q, n):
    xs = list(np.random.default_rng(n).exponential(1.0, n))
    assert percentile(xs, q) == pytest.approx(np.percentile(xs, q), rel=1e-12)


def test_percentile_of_hand_counted_samples():
    xs = [40.0, 10.0, 30.0, 20.0]
    assert percentile(xs, 50) == 25.0
    assert percentile(xs, 95) == pytest.approx(38.5)
    assert percentile([5.0], 95) == 5.0


def test_percentile_refuses_no_samples_and_bad_q():
    with pytest.raises(ValueError):
        percentile([], 50)
    with pytest.raises(ValueError):
        percentile([1.0], 101)


def test_drained_rate_counts_every_request_to_the_last_completion():
    # 8 requests sent from t=10; the last answer at t=14: 2 req/s,
    # whatever order the answers came in
    assert drained_rate(8, 10.0, [11.0, 14.0, 12.0]) == 2.0
    assert drained_rate(0, 10.0, []) is None
    with pytest.raises(ValueError):
        drained_rate(3, 10.0, [9.0])

