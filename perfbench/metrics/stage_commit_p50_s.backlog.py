"""Median over the pods created between the edges that were seen bound of committed -
commit_begin: PreBind and the sub-wave's update_wave (store and journal); the program's
recorder (utils/trace.py) joined to the client's record."""

from perfbench import programtrace


def read(rec):
    return programtrace.stage_p50(rec, "commit")
