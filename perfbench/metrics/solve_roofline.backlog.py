"""Least time the chip could take for the traced cycles' solves, by their shapes
(roofline.py: memory-bound), over the device time they took."""

from perfbench import reduce


def read(rec):
    return reduce.solve_roofline_share(rec)
