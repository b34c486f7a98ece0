"""Host time inside Store.create (the harness's span around the client's call)
per pod created between the interval's edges."""

from perfbench import reduce


def read(rec):
    secs, n = reduce.span_sum(rec, "store_create")
    return 1e6 * secs / n if n else None
