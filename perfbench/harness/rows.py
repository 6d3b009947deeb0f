"""From the benchmark's clock readings at a run loop's log rows to a
rate: the arithmetic of a window that is read row by row (``run_impala``
through ``log_fn``; any later family driven the same way).

Source: the contract's own advice for steadiness, "medians over the
whole window"; the whole-window quotient it replaces is
``bench.py::measure``'s (steps / elapsed), kept beside it in every run
file.
"""

from __future__ import annotations

import statistics


def _intervals(row_times):
    return [b - a for a, b in zip(row_times, row_times[1:])]


def steady_rate(row_times, steps_per_row: int) -> float:
    """Env steps a second from the clock readings of the log rows, each
    row ``steps_per_row`` env steps after the one before (the runner's
    ``env_steps`` check holds every interval to that): the steps of one
    interval over the median interval. A pause that lengthens fewer
    than half of the intervals does not move it."""
    intervals = _intervals(row_times)
    return steps_per_row / statistics.median(intervals)


def pause_share(row_times) -> float:
    """What ``steady_rate`` leaves out, in % of the rows' clock: the
    time between the first and the last row beyond as many median
    intervals as there are. 0.45 for one 76 ms pause in 17 s; a little
    under 0 where the intervals lean the other way."""
    intervals = _intervals(row_times)
    steady = len(intervals) * statistics.median(intervals)
    return 100.0 * (1.0 - steady / (row_times[-1] - row_times[0]))
