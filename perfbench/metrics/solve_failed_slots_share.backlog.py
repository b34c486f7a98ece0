"""Share of the solves' pod slots that ended without a placement (the harness's own record of
the solves, one entry a finalized solve: its pods minus the names it returned), over the solves
dispatched between the edges, in percent: what pods that no node admits take of the solver.
0 where every pod places.  None where no solve was dispatched between the edges."""

from perfbench import reduce


def read(rec):
    cyc = reduce.cycles(rec)
    pods = sum(c["pods"] for c in cyc)
    return 100.0 * sum(c["pods"] - c["placed"] for c in cyc) / pods if pods else None
