"""Median lateness of the CPU ledger's wakes between the edges (a proc.cpu.process row runs from
the sampler's timer to its first clock read), in ms: how long a thread that has work waits for
the interpreter.  None on a program without the CPU ledger."""

from perfbench import threadcpu


def read(rec):
    return threadcpu.interp_wait_ms_p50(rec)
