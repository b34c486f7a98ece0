"""Host time blocked on the readback (last_timings decode_wait_s) per cycle."""

from perfbench import reduce


def read(rec):
    cyc = reduce.cycles(rec)
    return 1e3 * sum(c.get("decode_wait_s", 0.0) for c in cyc) / len(cyc) if cyc else None
