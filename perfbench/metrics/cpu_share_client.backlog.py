"""Share of the process's CPU over the ledger's span between the edges (the first to the last
proc.cpu.process row) that the benchmark's own threads spent (perfbench-*: the creators, which
also delete, the watch and the generator; the client runs on the program's interpreter), in
percent (perfbench/threadcpu.py).  None on a program without the CPU ledger."""

from perfbench import threadcpu


def read(rec):
    return threadcpu.share(rec, "client")
