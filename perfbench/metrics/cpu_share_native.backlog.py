"""Share of the process's CPU over the ledger's span between the edges (the first to the last
proc.cpu.process row) that no Python thread the ledger read spent (XLA's and the runtime's own
threads, and threads that ended between two wakes), in percent (perfbench/threadcpu.py).  None
on a program without the CPU ledger."""

from perfbench import threadcpu


def read(rec):
    return threadcpu.share(rec, "native")
