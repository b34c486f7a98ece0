"""Thread-CPU seconds the event broadcaster spent writing events (span events.flush, one per
non-empty flush of the recorder's queue: a get and a create or update a bind's Scheduled event,
and every 256th write the TTL sweep) in the flushes that start between the edges, over the
seconds between the edges, in percent: the share of one interpreter the recorder takes from
the threads on a pod's path.  None on a program that has no such span."""

from perfbench import programtrace


def read(rec):
    spans = programtrace.spans_named(rec, ("events.flush",))
    if not spans:
        return None
    t0, t1 = programtrace.load(rec)["edges"]
    return 100.0 * sum(s["cpu1"] - s["cpu0"] for s in spans) / (t1 - t0)
