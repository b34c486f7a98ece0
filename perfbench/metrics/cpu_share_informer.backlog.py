"""Share of the process's CPU over the ledger's span between the edges (the first to the last
proc.cpu.process row) that the informers spent (informer-*: the watch events the scheduler's
cache and queue take in), in percent (perfbench/threadcpu.py).  None on a program without the
CPU ledger."""

from perfbench import threadcpu


def read(rec):
    return threadcpu.share(rec, "informer")
