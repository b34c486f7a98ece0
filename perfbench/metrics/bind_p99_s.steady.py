"""99th percentile of the window's bind latencies from the due time."""

from perfbench import reduce


def read(rec):
    return reduce.percentile(reduce.latencies(rec), 99)
