"""Share of the process's CPU over the ledger's span between the edges (the first to the last
proc.cpu.process row) that the commit stage spent (bind-wave and the commit pool's commit-
subwave_*: binds, Store.update_wave, post_bind), in percent (perfbench/threadcpu.py).  None on
a program without the CPU ledger."""

from perfbench import threadcpu


def read(rec):
    return threadcpu.share(rec, "commit")
