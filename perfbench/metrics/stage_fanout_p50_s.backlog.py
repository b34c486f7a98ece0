"""Median over the pods created between the edges that were seen bound of seen - committed:
from update_wave's return to the client's watch reading the bind (negative where the fan-out
beat the journal append); the program's recorder (utils/trace.py) joined to the client's
record."""

from perfbench import programtrace


def read(rec):
    return programtrace.stage_p50(rec, "fanout")
