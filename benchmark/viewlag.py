"""How stale the live view is, from the rank's step completions and the
poller's reads of ``/api/live``.

A poll sent at ``t`` that shows steps up to ``shown`` lags by ``t`` minus
the completion time of the oldest step that had completed by ``t`` and
that the view does not show yet, or 0 when it shows every completed step.
"""

from __future__ import annotations

import bisect
import statistics
from typing import List, Optional, Sequence, Tuple


def poll_lags(
    completions: Sequence[Tuple[int, float]],
    polls: Sequence[Tuple[float, Optional[int]]],
) -> List[float]:
    """``completions``: (step, monotonic seconds), steps rising with time;
    ``polls``: (send time, newest step shown or None). Seconds per poll."""
    steps = [s for s, _ in completions]
    times = [t for _, t in completions]
    lags = []
    for t, shown in polls:
        done = bisect.bisect_right(times, t)  # completions by t
        first_unshown = 0 if shown is None else bisect.bisect_right(steps, shown)
        lags.append(t - times[first_unshown] if first_unshown < done else 0.0)
    return lags


def p95(values: Sequence[float]) -> float:
    """95th percentile, linear between order statistics."""
    if len(values) == 1:
        return float(values[0])
    return statistics.quantiles(values, n=100, method="inclusive")[94]
