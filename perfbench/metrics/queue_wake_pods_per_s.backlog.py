"""Pods that cluster events moved out of the queue's unschedulable tier a second (row
sched.queue.wake, one a SchedulingQueue.move_for_event call that found pods parked: its n),
over the rows that start between the edges and the seconds between them.  0 where events come
and nothing is parked (the queue then writes nothing, and says so by the rows it does write at
every pop, sched.queue.depth).  None on a program whose queue writes neither."""

from perfbench import programtrace


def read(rec):
    if not programtrace.spans_named(rec, ("sched.queue.depth",)):
        return None
    t0, t1 = programtrace.load(rec)["edges"]
    rows = programtrace.spans_named(rec, ("sched.queue.wake",))
    return sum(s["n"] for s in rows) / (t1 - t0)
