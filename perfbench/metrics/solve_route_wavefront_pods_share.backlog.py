"""Share of the pods created between the edges whose solve took the wavefront route (the route
each pod's recorder row carries), in percent: the route this cell's `why` names."""

from perfbench import programtrace


def read(rec):
    return programtrace.route_pods_share(rec, "wavefront")
