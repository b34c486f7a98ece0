"""Wall time of the constraint-table build in one snapshot encode (span
sched.encode.constraints, one a cycle: the spread / term / preferred-term tables from the
bound-pod index), as the mean over the encodes that start between the edges, in ms.  None on a
program that has no such span."""

from perfbench import programtrace


def read(rec):
    spans = programtrace.spans_named(rec, ("sched.encode.constraints",))
    return 1e3 * sum(s["end"] - s["start"] for s in spans) / len(spans) if spans else None
