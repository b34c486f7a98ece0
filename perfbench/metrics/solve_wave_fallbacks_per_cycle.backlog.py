"""Pods a wavefront solve placed off its fast path (span sched.solve.waves: its a0 = members of
waves the device found coupled and ran serially, plus per-pod re-evaluations after a fit flip),
as the mean over the solves harvested between the edges.  None where no solve took the route,
and on a program that has no such span."""

from perfbench import programtrace


def read(rec):
    spans = programtrace.spans_named(rec, ("sched.solve.waves",))
    return sum(s["a0"] for s in spans) / len(spans) if spans else None
