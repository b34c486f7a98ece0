"""95th percentile of the window's bind latencies from the due time.  Per-layer
for now: its runs spread too widely to carry a bound (PERF.md, PR 24)."""

from perfbench import reduce


def read(rec):
    return reduce.percentile(reduce.latencies(rec), 95)
