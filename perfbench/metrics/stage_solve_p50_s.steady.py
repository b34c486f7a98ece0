"""Median over the pods due in the window that were seen bound of solved - popped: from the pop
to the names (the previous cycle's deferred finish, encode, dispatch, device, decode); the
program's recorder (utils/trace.py) joined to the client's record."""

from perfbench import programtrace


def read(rec):
    return programtrace.stage_p50(rec, "solve")
