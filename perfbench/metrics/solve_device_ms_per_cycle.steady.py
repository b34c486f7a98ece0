"""Device time of the solve programs in the traced slice, per cycle there."""

from perfbench import reduce


def read(rec):
    dev = reduce.solve_device_seconds(rec)
    n = len(reduce.trace_cycles(rec))
    return 1e3 * dev[0] / n if dev and n else None
