"""Node rows the device mirror re-sent (mirror.stats delta_rows_total) per cycle
over the window."""

from perfbench import reduce


def read(rec):
    rows = reduce.counter_delta(rec, "mirror_delta_rows_total")
    n = len([c for c in rec["cycles"] if rec["t_open"] <= c.get("t_dispatch0", -1.0) < rec["t_close"]])
    return rows / n if rows is not None and n else None
