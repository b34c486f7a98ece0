"""Median, over the pops between the edges, of the pods standing in the queue's backoff and
unschedulable tiers as the pop leaves them (row sched.queue.depth, one a pop that took pods, from
the counters the queue keeps: n active, a0 backoff, a1 unschedulable).  None on a program that
has no such row."""

from perfbench import programtrace, reduce


def read(rec):
    rows = programtrace.spans_named(rec, ("sched.queue.depth",))
    return reduce.percentile([s["a0"] + s["a1"] for s in rows], 50) if rows else None
