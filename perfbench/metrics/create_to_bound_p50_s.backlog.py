"""Median (client sees the bind) - (create issued) of the pods created between the
edges: in a closed loop it is backlog over rate and says nothing new."""

from perfbench import reduce


def read(rec):
    e = reduce.edges(rec)
    if e is None:
        return None
    lat = [rec["bound"][(ns, name)][0] - t for ns, name, _, t, _ in rec["created"]
           if e[0] <= t < e[1] and (ns, name) in rec["bound"]]
    return reduce.percentile(lat, 50)
