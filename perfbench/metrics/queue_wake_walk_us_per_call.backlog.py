"""Wall time of one SchedulingQueue.move_for_event call that found pods parked (row
sched.queue.wake, one such call: its length is the wait for the queue's lock and the walk of
every parked pod, whether it moved any or none), as the rows' seconds over their number, of the
rows that start between the edges, in us.  Most calls wake nobody (a bind's AssignedPodAdd wakes
no pod that failed on resources), so this is what a parked pod costs every event while it
stands.  None where no call found a pod parked, and on a program that has no such row."""

from perfbench import programtrace


def read(rec):
    rows = programtrace.spans_named(rec, ("sched.queue.wake",)) or ()
    return 1e6 * sum(s["end"] - s["start"] for s in rows) / len(rows) if rows else None
