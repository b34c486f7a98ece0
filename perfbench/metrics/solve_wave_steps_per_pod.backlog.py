"""In-wave sequential steps the device ran for one pod of a wavefront solve (row sched.solve.waves,
one per solve on that route: its a1, summed on the device over the solve's waves: the last valid
lane + 1 of a safe or coupled wave, 1 of a one-member wave), as the steps of the rows between the
edges over the pods of their cycles (span sched.cycle: its n).  1.0 is the floor, one step a pod;
a batch that leaves its bucket unfilled reads more (the pad pods are planned into waves too), and
a program whose every wave ran all 32 lanes reads 32 x waves / pods.  None where no solve took
the route, and on a program whose rows carry no step count."""

from perfbench import programtrace


def read(rec):
    rows = programtrace.spans_named(rec, ("sched.solve.waves",))
    if not rows:
        return None
    pods_of = {s["id"]: s["n"] for s in programtrace.spans_named(rec, ("sched.cycle",))}
    rows = [s for s in rows if s["cycle"] in pods_of]
    steps = sum(s["a1"] for s in rows)
    pods = sum(pods_of[c] for c in {s["cycle"] for s in rows})
    return steps / pods if steps and pods else None
