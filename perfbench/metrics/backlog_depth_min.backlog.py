"""Least (created - bound) the generator saw between the interval's edges.  Set
it against the mix's `backlog_pods`: in a mix that holds several batches
outstanding a reading under one batch (1,024) means the generator starved the
scheduler; in a mix that holds fewer than a batch it reads under that by design
and says how far the creators fell behind the binds."""

from perfbench import reduce


def read(rec):
    e = reduce.edges(rec)
    if e is None:
        return None
    inside = [d for t, d in rec["depth"] if e[0] <= t < e[1]]
    return float(min(inside)) if inside else None
