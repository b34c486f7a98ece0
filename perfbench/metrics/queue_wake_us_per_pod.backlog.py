"""Wall time of waking one parked pod (row sched.queue.wake: from before
SchedulingQueue.move_for_event asks for the queue's lock to after the last pod is pushed to
backoff or active), as the seconds of the rows that moved pods (n > 0) over their pods, of the
rows that start between the edges, in us.  A walk that woke nobody (n 0) is not in it: those are
queue_wake_walk_us_per_call.backlog's.  None where nothing was woken, and on a program that has
no such row."""

from perfbench import programtrace


def read(rec):
    rows = [s for s in programtrace.spans_named(rec, ("sched.queue.wake",)) or () if s["n"] > 0]
    pods = sum(s["n"] for s in rows)
    return 1e6 * sum(s["end"] - s["start"] for s in rows) / pods if pods else None
