"""Backend compile and cache-load seconds before the window opened."""

from perfbench import reduce


def read(rec):
    return float(sum(c[3] for c in reduce.compiles_between(rec, float("-inf"), rec["t_open"])))
