"""Median over the pods due in the window that were seen bound of popped - enqueued: in the
scheduling queue, the batch window included; the program's recorder (utils/trace.py) joined
to the client's record."""

from perfbench import programtrace


def read(rec):
    return programtrace.stage_p50(rec, "queue_wait")
