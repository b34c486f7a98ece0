"""Executables built or loaded from the cache (JAX's backend_compile events, which count both)
between Scheduler.warmup's return and the window's opening: the init pods, the bucket walk and
the warm replay.  What warmup was handed the templates of it covers, so the target is none; what
it was not handed (an init template of another shape) still loads here."""

from perfbench import reduce


def read(rec):
    return float(len(reduce.compiles_between(rec, rec["t_warmup_end"], rec["t_open"])))
