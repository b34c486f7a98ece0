"""Share of the pods with inter-pod term rows whose match of a term they require was not a write
in the wave plan (row sched.plan.terms, one a plan whose batch has term rows: n pods that carry
or match a term, a0 those whose match of a required affinity term was saturated, the term having
a matching pod somewhere at the batch's start), as 100 x the a0 over the n of the rows that start
between the edges, in %.  About 100 where every pod requires the zone term its peers match; 0.0
where the terms are anti-affinity alone.  None where no plan had term rows, and on a program
that has no such row."""

from perfbench import programtrace


def read(rec):
    rows = programtrace.spans_named(rec, ("sched.plan.terms",)) or ()
    n = sum(s["n"] for s in rows)
    return 100.0 * sum(s["a0"] for s in rows) / n if n else None
