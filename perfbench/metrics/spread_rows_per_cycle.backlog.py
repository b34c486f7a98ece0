"""Spread rows in one batch's constraint tables (span sched.encode.classes, one a cycle: its n;
one row per distinct constraint and namespace among the batch's pods), as the mean over the
encodes that start between the edges.  0 where no pod carries a spread constraint.  None on a
program that has no such span."""

from perfbench import programtrace


def read(rec):
    spans = programtrace.spans_named(rec, ("sched.encode.classes",))
    return sum(s["n"] for s in spans) / len(spans) if spans else None
