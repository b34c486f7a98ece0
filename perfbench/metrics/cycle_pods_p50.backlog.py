"""Median pods per scheduling cycle (one solve) between the edges."""

from perfbench import reduce


def read(rec):
    return reduce.percentile([c["pods"] for c in reduce.cycles(rec)], 50)
