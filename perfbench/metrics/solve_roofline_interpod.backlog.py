"""Least time the chip could take for the traced cycles' solves where pods carry inter-pod terms,
by the deployment's shapes alone (roofline_interpod.py: the plain solve's bytes plus the two term
tables read once, T the valid term rows of the cycle's own sched.encode.terms row; memory-bound),
over the device time they took, in percent.  The arithmetic is reduce.solve_roofline_share's.
None without a device trace, and on a program that has no such row."""

from perfbench import peaks, programtrace, reduce, roofline_interpod


def read(rec):
    dev = reduce.solve_device_seconds(rec)
    rows = programtrace.spans_named(rec, ("sched.encode.terms",))
    if dev is None or dev[0] <= 0 or not rows:
        return None
    # a cycle's row is written inside its encode, so between its dispatch's two ends
    cyc = []
    for c in reduce.trace_cycles(rec):
        if "P" not in c:
            continue
        mine = [s["n"] for s in rows if c["t_dispatch0"] <= s["start"] <= c["t_dispatch1"]]
        if mine:
            cyc.append((c, int(mine[0])))
    if not cyc:
        return None
    bw = peaks.peak(rec["device"]["kind"])["hbm_bytes_per_s"]
    least = sum(
        roofline_interpod.solve_min_seconds(c["P"], c["N"], c["R"], t, bw) for c, t in cyc
    )
    # the solve programs run once a cycle: the traced executions' mean time, times the
    # cycles whose shapes and rows were recorded inside the slice
    per_exec = dev[0] / max(dev[1], 1)
    return 100.0 * least / (per_exec * len(cyc))
