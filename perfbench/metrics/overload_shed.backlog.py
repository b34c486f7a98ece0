"""Pods the overload ladder shed (overload_shed_total) over the window."""

from perfbench import reduce


def read(rec):
    return reduce.counter_delta(rec, "overload_shed")
