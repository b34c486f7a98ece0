"""Snapshot encode (last_timings encode_s) per cycle in the window."""

from perfbench import reduce


def read(rec):
    cyc = reduce.cycles(rec)
    return 1e3 * sum(c.get("encode_s", 0.0) for c in cyc) / len(cyc) if cyc else None
