"""Commit time (bind waves into Store.update_wave) not overlapped by a solve of
the scheduling thread, per pod bound between the edges."""

from perfbench import reduce


def read(rec):
    pods = reduce.pods_bound_between(rec)
    return 1e6 * reduce.commit_exposed_seconds(rec) / pods if pods else None
