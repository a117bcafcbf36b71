"""View-lag arithmetic on synthetic timelines."""

import pytest

from benchmark.viewlag import p95, poll_lags

DONE = [(1, 10.0), (2, 10.5), (3, 11.0), (4, 11.5)]


@pytest.mark.parametrize(
    "poll,lag",
    [
        ((9.0, None), 0.0),      # nothing completed yet
        ((10.2, None), 0.2),     # step 1 done at 10.0, nothing shown
        ((10.7, 1), 0.2),        # shows 1; step 2 done at 10.5
        ((10.7, 2), 0.0),        # shows everything completed
        ((11.6, 1), 1.1),        # oldest unshown is step 2
        ((11.6, 4), 0.0),
        ((11.2, 3), 0.0),        # step 4 not complete yet
    ],
)
def test_poll_lag(poll, lag):
    assert poll_lags(DONE, [poll]) == [pytest.approx(lag)]


def test_p95_linear():
    assert p95(list(range(101))) == pytest.approx(95.0)
    assert p95([3.0]) == 3.0
