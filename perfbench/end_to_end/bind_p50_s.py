"""Median, over all pods due in the window, of (the client sees the bind) - (the
pod was due to be created)."""

from perfbench import reduce


def read(rec):
    return reduce.percentile(reduce.latencies(rec), 50)
