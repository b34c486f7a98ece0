"""CPU seconds the whole process spent over the ledger's span between the edges (the first to
the last proc.cpu.process row), over the pods due in that span, in us.  None on a program
without the CPU ledger."""

from perfbench import threadcpu


def read(rec):
    return threadcpu.host_cpu_us_per_pod(rec)
