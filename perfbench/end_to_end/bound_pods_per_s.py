"""Pods of the whole bind waves the client saw start inside the window, over the
time from the first of those waves to the wave that ends the window.  All the
work over all the time: every stall between the two edges is in it."""

from perfbench import reduce


def read(rec):
    iv = reduce.interval(rec)
    if iv is None or iv.t_end <= iv.t_start:
        return None
    return iv.pods / (iv.t_end - iv.t_start)
