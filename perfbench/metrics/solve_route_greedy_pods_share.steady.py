"""Share of the pods due in the window whose solve took the greedy scan (the route each pod's
recorder row carries), in percent: the route this cell's `why` names."""

from perfbench import programtrace


def read(rec):
    return programtrace.route_pods_share(rec, "greedy")
