"""Bind events grouped into waves, and the whole-wave interval of a window.

The commit stage binds a batch at a time, so the client sees binds in bursts.
A count of binds between two fixed instants is quantised by one burst; at a
thousand pods a burst that is a few percent of a window.  So the interval is
snapped to bursts: it runs from the first event of the first wave that starts
at or after the window opens to the first event of the first wave that starts
at or after it closes, and counts the pods of the waves that start between.
No partial wave at either end; every stall between the two edges is inside.

Waves are told apart by the gap between events: the binds of one cycle reach
the client within milliseconds of each other, and the next cycle's encode,
solve and decode lie between two cycles' binds.  The gap is a parameter of
the traffic file.
"""

from __future__ import annotations

from typing import NamedTuple


class Wave(NamedTuple):
    t_first: float
    t_last: float
    pods: int


class Interval(NamedTuple):
    t_start: float
    t_end: float
    pods: int
    waves: int


def group_waves(times, gap_s: float) -> list:
    """`times`: arrival times of bind events in arrival order."""
    waves = []
    first = last = None
    n = 0
    for t in times:
        if first is None:
            first, last, n = t, t, 1
        elif t - last > gap_s:
            waves.append(Wave(first, last, n))
            first, last, n = t, t, 1
        else:
            last = max(last, t)
            n += 1
    if first is not None:
        waves.append(Wave(first, last, n))
    return waves


def whole_wave_interval(waves, t_open: float, t_close: float):
    """The snapped interval, or None where no wave starts at or after the
    window's close (the run has to keep the load on until one does) or none
    starts inside."""
    start = next((i for i, w in enumerate(waves) if w.t_first >= t_open), None)
    end = next((i for i, w in enumerate(waves) if w.t_first >= t_close), None)
    if start is None or end is None or end <= start:
        return None
    inside = waves[start:end]
    return Interval(
        inside[0].t_first, waves[end].t_first,
        sum(w.pods for w in inside), len(inside),
    )


def closing_wave_seen(times, gap_s: float, t_close: float) -> bool:
    """Has a wave that starts at or after `t_close` begun?  (What the run
    waits for before it takes the load off.)"""
    prev = None
    for t in times:
        if t >= t_close and (prev is None or t - prev > gap_s):
            return True
        prev = t
    return False
