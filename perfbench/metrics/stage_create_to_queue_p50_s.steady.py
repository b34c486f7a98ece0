"""Median over the pods due in the window that were seen bound of enqueued - issued: from the
client's create to the scheduler's queue admitting the pod (store publish, watch dispatch,
the Pod informer's handler); the program's recorder (utils/trace.py) joined to the client's
record."""

from perfbench import programtrace


def read(rec):
    return programtrace.stage_p50(rec, "create_to_queue")
