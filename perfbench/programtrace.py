"""What the program's own flight recorder (``kubernetes_tpu/utils/trace.py``)
holds of a run, joined to what the client saw.

The readers run in the run's own process after the window, so the recorder's
rings still hold the run.  A pod's path is six stamps inside the program plus
the client's two ends, all on ``time.perf_counter``; with ``issued`` the
instant the harness records for the create (``rec["created"]``; in a closed
loop that is when the call returned, so the first stage can be negative) and
``seen`` the client's own observation of the bind, they telescope exactly:

    seen - issued = (enqueued - issued) + (popped - enqueued) + (solved - popped)
                  + (commit_begin - solved) + (committed - commit_begin)
                  + (seen - committed)

(the watch fan-out runs beside the journal append, so the last term is often
negative: the client sees the bind before ``update_wave`` has returned).

Everything here returns None, and never raises, where the program has no
recorder (a parent commit, the plain reference) or a ring lapped into the
run: the result line then leaves the metric out.
"""

from __future__ import annotations

from . import reduce

STAGES = ("create_to_queue", "queue_wait", "solve", "commit_wait", "commit", "fanout")
# the scheduling lane's working spans: the designed waits (pop_wait,
# decode_wait) are left out
LANE_SPANS = ("sched.encode", "sched.dispatch", "sched.stage", "sched.wave_handoff",
              "sched.postfilter")


def load(rec):
    """{"spans": [dict], "pods": {(ns, name): dict}, "routes", "dropped_*"}
    of the run between its edges, or None.  Kept on the record: nineteen
    readers share one snapshot."""
    if "_programtrace" in rec:
        return rec["_programtrace"]
    rec["_programtrace"] = out = _load(rec)
    return out


def _load(rec):
    e = reduce.edges(rec)
    if e is None:
        return None
    try:
        from kubernetes_tpu.utils import trace

        # pods enqueued from the first edge on (a pod created just before the
        # second edge is popped after it); spans that start between the edges
        snap = trace.snapshot(e[0], float("inf"))
        span_fields, pod_fields = trace.SPAN_FIELDS, trace.POD_FIELDS
    except (ImportError, AttributeError):
        return None     # a program without the recorder
    if snap is None:
        return None     # a ring lapped into the run
    spans = [dict(zip(span_fields, row)) for row in snap["spans"]]
    by_key = {}
    for row in snap["pods"]:
        d = dict(zip(pod_fields, row))
        ns, _, name = str(d["key"]).partition("/")
        by_key[(ns, name)] = d      # a key created again: the newest row
    return {
        "edges": e,
        "spans": [s for s in spans if s["start"] < e[1]],
        "pods": by_key,
        "routes": snap["routes"],
        "dropped_spans": snap["dropped_spans"],
        "dropped_pods": snap["dropped_pods"],
    }


def population(rec) -> list:
    """[(key, issued)]: in an open loop the pods due in the window, in a
    closed loop the pods created between the edges."""
    if rec["kind"] == "backlog":
        e = reduce.edges(rec)
        if e is None:
            return []
        return [((ns, name), t) for ns, name, _, t, _ in rec["created"] if e[0] <= t < e[1]]
    return [((ns, name), issued) for ns, name, _, issued, _ in rec["due"] if issued is not None]


def paths(rec):
    """One dict per pod of the population that the client saw bound and the
    recorder holds whole: the six stage durations, ``issued``, ``seen`` and
    the row.  None without a recorder."""
    pt = load(rec)
    if pt is None:
        return None
    out = []
    for key, issued in population(rec):
        seen = rec["bound"].get(key)
        row = pt["pods"].get(key)
        if seen is None or row is None:
            continue    # never bound: already `failed` end to end
        marks = [issued, row["enqueued"], row["popped"], row["solved"],
                 row["commit_begin"], row["committed"], seen[0]]
        if None in marks:
            continue    # bound outside a wave (a Permit thread): no whole path
        d = {s: b - a for s, a, b in zip(STAGES, marks, marks[1:])}
        d.update(key=key, issued=issued, seen=seen[0], row=row)
        out.append(d)
    return out


def stage_p50(rec, stage: str):
    ps = paths(rec)
    return reduce.percentile([p[stage] for p in ps], 50) if ps else None


def residual(rec):
    """Largest |(seen - issued) - sum of the six stages| over the paths, in
    seconds: the identity holds to the float."""
    ps = paths(rec)
    if not ps:
        return None
    return max(abs((p["seen"] - p["issued"]) - sum(p[s] for s in STAGES)) for p in ps)


def spans_named(rec, names, direct_only: bool = False):
    """The closed spans of `names` that start between the edges
    (`direct_only`: children of their cycle's root alone, so a hand-off
    nested in a staging span is not counted twice)."""
    pt = load(rec)
    if pt is None:
        return None
    names = set(names)
    return [s for s in pt["spans"] if s["name"] in names and s["end"] is not None
            and (not direct_only or s["parent"] == s["cycle"])]


def offcpu_share(rec, names, direct_only: bool = False):
    """Sum of (wall - the thread's own CPU time) over the sum of wall of the
    spans, in percent: time a thread wanted to run and did not."""
    ss = [s for s in spans_named(rec, names, direct_only) or ()
          if s["cpu0"] is not None and s["cpu1"] is not None]
    if not ss:
        return None
    wall = sum(s["end"] - s["start"] for s in ss)
    cpu = sum(s["cpu1"] - s["cpu0"] for s in ss)
    return 100.0 * max(wall - cpu, 0.0) / wall if wall > 0 else None


def lock_wait_ms_per_cycle(rec):
    waits = spans_named(rec, ("sched.encode.lock_wait",))
    cycles = spans_named(rec, ("sched.cycle",))
    if not cycles:
        return None
    return 1e3 * sum(s["end"] - s["start"] for s in waits) / len(cycles)


def route_pods_share(rec, route: str):
    """Share of the population's solved pods whose (last) solve took
    `route`, in percent, from the route each pod's row carries."""
    pt = load(rec)
    if pt is None or route not in pt["routes"]:
        return None
    want = pt["routes"].index(route)
    routes = [pt["pods"][k]["route"] for k, _ in population(rec)
              if k in pt["pods"] and pt["pods"][k]["route"] >= 0]
    return 100.0 * sum(1 for r in routes if r == want) / len(routes) if routes else None
