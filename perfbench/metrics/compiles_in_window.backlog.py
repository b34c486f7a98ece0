"""Executables built or loaded from the cache (JAX's backend_compile events,
which count both) between the window's edges.  The target is none."""

from perfbench import reduce


def read(rec):
    return float(len(reduce.compiles_between(rec, rec["t_open"], rec["t_close"])))
