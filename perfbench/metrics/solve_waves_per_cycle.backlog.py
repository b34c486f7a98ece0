"""Waves the device ran in one wavefront solve (span sched.solve.waves, one per solve on that
route: its n, read back with the placements), as the mean over the solves harvested between the
edges.  About pods / 32 where no pod couples with another; up to one a pod where pods of one
namespace share a spread row.  None where no solve took the route, and on a program that has
no such span."""

from perfbench import programtrace


def read(rec):
    spans = programtrace.spans_named(rec, ("sched.solve.waves",))
    return sum(s["n"] for s in spans) / len(spans) if spans else None
