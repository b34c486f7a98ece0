"""1 - (union of the device's operation intervals) over the traced slice of the
window, in percent."""

from perfbench import reduce


def read(rec):
    return reduce.device_idle_share(rec)
