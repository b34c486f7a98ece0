"""Snapshot encode (last_timings encode_s) per pod solved between the edges."""

from perfbench import reduce


def read(rec):
    cyc = reduce.cycles(rec)
    pods = sum(c["pods"] for c in cyc)
    return 1e6 * sum(c.get("encode_s", 0.0) for c in cyc) / pods if pods else None
