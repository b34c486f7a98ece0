"""Valid rows of one batch's inter-pod term tables (row sched.encode.terms, one an encode: its n;
one row per distinct required (anti-)affinity term among the batch's pods and the bound pods
that own one), as the mean over the encodes that start between the edges.  0 where no pod
carries a term.  None on a program that has no such row."""

from perfbench import programtrace


def read(rec):
    rows = programtrace.spans_named(rec, ("sched.encode.terms",))
    return sum(s["n"] for s in rows) / len(rows) if rows else None
