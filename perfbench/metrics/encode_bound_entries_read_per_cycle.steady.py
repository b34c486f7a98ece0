"""Bound-pod entries the constraint tables read in one snapshot encode (span
sched.encode.constraints, one a cycle: its n), as the mean over the encodes that start
between the edges.  0 where no batch brought a constraint row and no bound pod owns a term;
the number of bound pods where every encode read them all.  None on a program that has no
such span."""

from perfbench import programtrace


def read(rec):
    spans = programtrace.spans_named(rec, ("sched.encode.constraints",))
    return sum(s["n"] for s in spans) / len(spans) if spans else None
