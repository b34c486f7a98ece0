"""Device time of the solve programs in the traced slice, per pod solved there."""

from perfbench import reduce


def read(rec):
    dev = reduce.solve_device_seconds(rec)
    pods = sum(c["pods"] for c in reduce.trace_cycles(rec))
    return 1e6 * dev[0] / pods if dev and pods else None
