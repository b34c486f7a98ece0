"""Wall time of a cycle's failure branch for one pod that ended without a bind (row sched.fail,
one a cycle that had such pods: n of them, a0 the seconds from before each one's failure stamp to
after it was parked or re-queued and its FailedScheduling event was handed to the recorder), as
the rows' seconds over their pods, of the rows that start between the edges, in us.  None where
every pod bound, and on a program that has no such row."""

from perfbench import programtrace


def read(rec):
    rows = programtrace.spans_named(rec, ("sched.fail",)) or ()
    pods = sum(s["n"] for s in rows)
    return 1e6 * sum(s["a0"] for s in rows) / pods if pods else None
