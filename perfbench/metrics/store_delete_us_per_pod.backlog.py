"""Wall time of one Store.delete on the client's threads (tally store.delete: one interval a
call, summed into one row a thread and tenth of a second: n calls, a0 their seconds), as the
seconds over the calls of the rows that start between the edges, in us: what a creator pays to
delete, apart from what it pays to create (store_create_us_per_pod.backlog).  None where
nothing was deleted, and on a program that has no such tally."""

from perfbench import programtrace


def read(rec):
    rows = programtrace.spans_named(rec, ("store.delete",)) or ()
    pods = sum(s["n"] for s in rows)
    return 1e6 * sum(s["a0"] for s in rows) / pods if pods else None
