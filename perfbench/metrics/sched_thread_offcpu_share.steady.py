"""Wall minus the thread's own CPU time over wall, summed over the scheduling lane's working
spans (sched.encode, dispatch, stage, wave_handoff, postfilter; the designed waits pop_wait
and decode_wait left out), in percent: time the lane wanted to run and did not."""

from perfbench import programtrace


def read(rec):
    return programtrace.offcpu_share(rec, programtrace.LANE_SPANS, direct_only=True)
