"""Wall minus the thread's own CPU time over wall of the commit workers' sched.commit spans
between the edges, in percent: where a convoy on a lock shows as waiting."""

from perfbench import programtrace


def read(rec):
    return programtrace.offcpu_share(rec, ("sched.commit",))
