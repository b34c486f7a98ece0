"""99th percentile of (create issued) - (due): how late the generator ran."""

from perfbench import reduce


def read(rec):
    return reduce.percentile(reduce.lateness(rec), 99)
