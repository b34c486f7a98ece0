"""Seconds the encode waited for the scheduler cache's lock (span sched.encode.lock_wait; a
concurrent wave commit holds it) per scheduling cycle between the edges, in ms."""

from perfbench import programtrace


def read(rec):
    return programtrace.lock_wait_ms_per_cycle(rec)
