"""Pods the client saw bound inside the window over its length: below the knee it
is the offered rate."""

from perfbench import reduce


def read(rec):
    return reduce.pods_bound_between(rec) / (rec["t_close"] - rec["t_open"])
