"""Share of the time between the edges in which the interpreter's collector ran
(it stops every thread), in percent, from gc.callbacks."""

from perfbench import reduce


def read(rec):
    return reduce.gc_pause_share(rec)
