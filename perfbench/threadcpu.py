"""Where the process's CPU went, by thread, from the program's CPU ledger
(``kubernetes_tpu/utils/trace.py``: rows ``proc.cpu``, one a thread whose
CPU clock advanced since its last row, and ``proc.cpu.process``, one a wake
of the sampler, from its timer to its first clock read).

The span is the ledger's own: from the first to the last ``proc.cpu.process``
row between the edges (``reduce.edges``).  A thread's CPU over it is the sum
of its rows' advances after the first wake up to the last, so a thread that
starts or ends inside counts only what it spent there; the process's is the
change of its clock from the first wake to the last.  Threads are grouped by
name, and a share is a group's CPU over the process's, in percent.  What no
Python thread holds (XLA's and the runtime's own threads, threads that ended
between two wakes) is ``native``.  The event broadcaster and the collector
have a metric of their own each (``events_cpu_share.*``, ``gc_pause_share.*``)
and no group here.

Everything returns None, and never raises, where the program has no ledger (a
parent commit, the plain reference) or a ring lapped into the run: the result
line then leaves the metric out.
"""

from __future__ import annotations

from . import programtrace, reduce

# group -> (whole names, name prefixes)
GROUPS = {
    "client": ((), ("perfbench-",)),            # creators and deleters, watch, generator
    "lane": (("scheduler",), ("scheduler-lane",)),
    "commit": (("bind-wave",), ("commit-subwave",)),
    "informer": ((), ("informer-",)),
    "watch_dispatch": ((), ("watch-dispatch-",)),
}


def group_of(name: str):
    """The group a thread of this name belongs to, or None."""
    for group, (whole, prefixes) in GROUPS.items():
        if name in whole or name.startswith(prefixes):
            return group
    return None


def load(rec):
    """{"t0", "t1", "process_cpu", "threads": {key: (name, cpu)}, "late": [s]}
    of the ledger's span inside the edges, or None.  Kept on the record."""
    if "_threadcpu" not in rec:
        rec["_threadcpu"] = _load(rec)
    return rec["_threadcpu"]


def _load(rec):
    pt = programtrace.load(rec)
    if pt is None:
        return None
    wakes = sorted((s for s in pt["spans"] if s["name"] == "proc.cpu.process"),
                   key=lambda s: s["end"])
    if len(wakes) < 2:
        return None     # a program without the ledger
    try:
        from kubernetes_tpu.utils import trace

        names = trace.thread_names()
    except (ImportError, AttributeError):
        return None
    t0, t1 = wakes[0]["end"], wakes[-1]["end"]
    cpu = {}
    for s in pt["spans"]:
        if s["name"] == "proc.cpu" and t0 < s["start"] <= t1:
            cpu[s["n"]] = cpu.get(s["n"], 0.0) + s["cpu1"] - s["cpu0"]
    return {
        "t0": t0, "t1": t1,
        "process_cpu": wakes[-1]["cpu1"] - wakes[0]["cpu1"],
        "threads": {key: (names.get(key, "?"), c) for key, c in cpu.items()},
        "late": [s["end"] - s["start"] for s in wakes],
    }


def group_cpu(rec):
    """{group: CPU seconds} over the span, ``native`` included; None without
    a ledger."""
    led = load(rec)
    if led is None:
        return None
    out = {g: 0.0 for g in GROUPS}
    for name, c in led["threads"].values():
        g = group_of(name)
        if g is not None:
            out[g] += c
    # every thread's reads precede the process's in a wake, so this is the
    # process's CPU outside the Python threads read, to the microsecond
    out["native"] = max(0.0, led["process_cpu"] - sum(c for _, c in led["threads"].values()))
    return out


def share(rec, group: str):
    """A group's CPU over the process's, in percent."""
    led, by = load(rec), group_cpu(rec)
    if by is None or led["process_cpu"] <= 0:
        return None
    return 100.0 * by[group] / led["process_cpu"]


def host_cpu_us_per_pod(rec):
    """The process's CPU over the span, over the pods of the span: in a
    closed loop those the client saw bound, in an open one those due."""
    led = load(rec)
    if led is None:
        return None
    t0, t1 = led["t0"], led["t1"]
    if rec["kind"] == "backlog":
        pods = sum(1 for b in rec["bind_log"] if t0 < b[0] <= t1)
    else:
        pods = sum(1 for d in rec["due"] if t0 < d[4] <= t1)
    return 1e6 * led["process_cpu"] / pods if pods else None


def interp_wait_ms_p50(rec):
    """The median lateness of the sampler's wakes in the span, in ms: how long
    a thread that wants the interpreter waits for it."""
    led = load(rec)
    return None if led is None else 1e3 * reduce.percentile(led["late"], 50)
