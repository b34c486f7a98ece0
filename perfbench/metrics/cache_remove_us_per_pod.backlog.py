"""Wall time of one bound pod's removal from the scheduler cache on the informer thread (tally
sched.cache.remove: one interval a removed pod around SchedulerCache.remove_pod, its wait for
cache.lock included, summed into one row a tenth of a second: n pods, a0 their seconds), as the
seconds over the pods of the rows that start between the edges, in us.  None where nothing was
removed, and on a program that has no such tally."""

from perfbench import programtrace


def read(rec):
    rows = programtrace.spans_named(rec, ("sched.cache.remove",)) or ()
    pods = sum(s["n"] for s in rows)
    return 1e6 * sum(s["a0"] for s in rows) / pods if pods else None
