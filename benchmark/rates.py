"""How a rate is read: from the timestamps of completed work.

Pure arithmetic on timestamps, so the tests can drive it without a device.
A training loop dispatches steps and fetches the loss every ``log_every``
steps; every fetch is a timestamp and the steps between two fetches are one
sub-window.  The rate a user pays for is all the work of the window over
all its time, first fetch stamp to last: a stall inside the window is part
of it.  Nothing here is divided by ``--seconds``.  The median sub-window is
the steady step, a per-layer number beside it.
"""

from __future__ import annotations

import statistics
from typing import Callable, List, Sequence


def run_window(do_steps: Callable[[int], None], fetch: Callable[[], float],
               log_every: int, seconds: float,
               clock: Callable[[], float]) -> List[float]:
    """Drive the measured window; return the fetch timestamps.

    ``do_steps(n)`` dispatches ``n`` steps without blocking, ``fetch()``
    blocks on the newest loss.  The first timestamp is the window's start
    (taken right after a fetch, so the device queue is empty); the window
    ends at the first fetch at or after ``seconds``.
    """
    stamps = [clock()]
    while stamps[-1] - stamps[0] < seconds:
        do_steps(log_every)
        fetch()
        stamps.append(clock())
    return stamps


def durations(stamps: Sequence[float]) -> List[float]:
    return [b - a for a, b in zip(stamps, stamps[1:])]


def window_rate(stamps: Sequence[float], items_per_subwindow: float,
                chips: int) -> float:
    """Items of all sub-windows / (last fetch stamp - first) / chips."""
    return (items_per_subwindow * (len(stamps) - 1)
            / (stamps[-1] - stamps[0]) / chips)


def median_step_s(stamps: Sequence[float], log_every: int) -> float:
    """Median sub-window duration over the steps in a sub-window."""
    return statistics.median(durations(stamps)) / log_every


def distribution(stamps: Sequence[float], items_per_subwindow: float,
                 chips: int) -> dict:
    """The whole-window rate beside min / quartiles / max of the sub-window
    rates and the slowest sub-windows by position: tells a stall inside a
    run (the median drops it) from something fixed per process (it does
    not)."""
    d = durations(stamps)
    rates = sorted(items_per_subwindow / x / chips for x in d)
    q1, q2, q3 = (statistics.quantiles(rates, n=4) if len(rates) > 1
                  else (rates[0],) * 3)
    return {
        "subwindows": len(d),
        "window_s": stamps[-1] - stamps[0],
        "mean_rate": window_rate(stamps, items_per_subwindow, chips),
        "median_rate": q2,
        "min": rates[0], "q1": q1, "q3": q3, "max": rates[-1],
        "slowest": [[i, d[i]] for i in
                    sorted(range(len(d)), key=lambda i: -d[i])[:3]],
    }
