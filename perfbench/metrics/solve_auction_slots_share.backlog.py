"""Share of the solves' pod slots whose solve took the auction route (the harness's own record
of the solves: the effective solve's route and its pods), over the solves dispatched between the
edges, in percent.  The pods' own rows cannot say it here: the pods that fill the 1,024-pod
batches were created before the window.  0 where no batch reaches the auction's threshold.
None where no solve was dispatched between the edges."""

from perfbench import reduce


def read(rec):
    cyc = reduce.cycles(rec)
    pods = sum(c["pods"] for c in cyc)
    return 100.0 * sum(c["pods"] for c in cyc if c.get("route") == "auction") / pods if pods else None
