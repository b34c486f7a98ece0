"""CPU seconds the whole process spent (every thread: the program's, the client's on the same
interpreter, XLA's own) over the ledger's span between the edges (the first to the last
proc.cpu.process row), over the pods the client saw bound in that span, in us.  Times the
cell's pods/s it reads the cores the process keeps busy: about 1,000,000 is one interpreter's
worth.  None on a program without the CPU ledger."""

from perfbench import threadcpu


def read(rec):
    return threadcpu.host_cpu_us_per_pod(rec)
