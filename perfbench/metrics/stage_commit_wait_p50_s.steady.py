"""Median over the pods due in the window that were seen bound of commit_begin - solved:
staging, Permit and the hand-off, until a commit worker starts on the pod's wave; the
program's recorder (utils/trace.py) joined to the client's record."""

from perfbench import programtrace


def read(rec):
    return programtrace.stage_p50(rec, "commit_wait")
