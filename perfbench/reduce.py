"""Arithmetic the metric readers share: percentiles, the whole-wave interval
of a run, latencies from due times, and sums over the window's cycles and
spans.  Each reader (one file per metric) is a few lines over these."""

from __future__ import annotations

import math

from . import peaks, roofline, tracered, waves


def percentile(values, q: float):
    """The q-th percentile (0..100) by linear interpolation; None if empty."""
    vs = sorted(values)
    if not vs:
        return None
    k = (len(vs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return vs[lo] + (vs[hi] - vs[lo]) * (k - lo)


# -- the closed loop: whole waves --------------------------------------------------

def waves_of(rec) -> list:
    """The run's bind events grouped into waves by the mix's gap."""
    return waves.group_waves([b[0] for b in rec["bind_log"]], float(rec["params"]["wave_gap_s"]))


def interval(rec):
    """The snapped interval of the run's window (see waves.py), or None."""
    return waves.whole_wave_interval(waves_of(rec), rec["t_open"], rec["t_close"])


def edges(rec):
    """The instants between which a cell's counts are taken: the snapped
    interval in a closed loop, the window in an open one."""
    if rec["kind"] == "backlog":
        iv = interval(rec)
        return (iv.t_start, iv.t_end) if iv else None
    return rec["t_open"], rec["t_close"]


def pods_bound_between(rec) -> int:
    e = edges(rec)
    if e is None:
        return 0
    return sum(1 for b in rec["bind_log"] if e[0] <= b[0] < e[1])


# -- the open loop: latency from the due time --------------------------------------

def latencies(rec) -> list:
    """(observed bind) - (due), over every pod due in the window; a pod not
    bound by the drain deadline counts as the worst: the deadline itself."""
    out = []
    for ns, name, _, _, due in rec["due"]:
        seen = rec["bound"].get((ns, name))
        out.append((seen[0] if seen else rec["t_drained"]) - due)
    return out


def lateness(rec) -> list:
    """(create issued) - (due); a pod never sent counts to the drain's end."""
    return [(rec["t_drained"] if issued is None else issued) - due
            for _, _, _, issued, due in rec["due"]]


# -- cycles, spans, counters -------------------------------------------------------

def live_range(rec, t0: float, t1: float):
    """Where pods complete: the least, the median and the most that the
    generator's samples between two instants read of (pods bound and not handed
    out for deletion, pods bound and not seen deleted).  None where nothing
    completes."""
    inside = [s for s in rec["live"] if t0 <= s[0] < t1]
    if not inside:
        return None
    return {
        name: [min(v), percentile(v, 50), max(v)]
        for name, v in (("not_handed_out", [s[1] for s in inside]),
                        ("not_seen_deleted", [s[2] for s in inside]))
    }


def cycles(rec) -> list:
    e = edges(rec)
    if e is None:
        return []
    return [c for c in rec["cycles"] if e[0] <= c.get("t_dispatch0", -1.0) < e[1]]


def span_sum(rec, name: str):
    """(seconds, count of n) of the spans of `name` that start between the
    edges."""
    e = edges(rec)
    if e is None:
        return 0.0, 0
    hit = [(t1 - t0, n) for t0, t1, n in rec["spans"].get(name, ()) if e[0] <= t0 < e[1]]
    return sum(d for d, _ in hit), sum(n for _, n in hit)


def counter_delta(rec, key: str):
    a, b = rec["counters_open"].get(key), rec["counters_close"].get(key)
    return None if a is None or b is None else b - a


def compiles_between(rec, t0: float, t1: float) -> list:
    return [c for c in rec["compiles"] if c[1] == "backend_compile" and t0 <= c[0] < t1]


def gc_seconds_between(rec, t0: float, t1: float, generation=None) -> float:
    """Seconds of [t0, t1) in which the interpreter's collector ran."""
    return sum(
        min(s + d, t1) - max(s, t0) for s, d, gen in rec["gc_pauses"]
        if s < t1 and s + d > t0 and generation in (None, gen)
    )


def gc_pause_share(rec):
    """Share of the time between the edges in which the collector ran (it
    stops every thread), in percent."""
    e = edges(rec)
    return None if e is None else 100.0 * gc_seconds_between(rec, *e) / (e[1] - e[0])


def commit_exposed_seconds(rec) -> float:
    """Commit time that no solve (dispatch to end of decode) of the scheduling
    thread overlapped."""
    e = edges(rec)
    if e is None:
        return 0.0
    solves = tracered.union(
        (c["t_dispatch0"], c["t_decode1"]) for c in rec["cycles"] if "t_dispatch0" in c
    )
    exposed = 0.0
    for t0, t1, _ in rec["spans"].get("commit", ()):
        if not e[0] <= t0 < e[1]:
            continue
        hidden = sum(max(0.0, min(t1, s1) - max(t0, s0)) for s0, s1 in solves)
        exposed += (t1 - t0) - hidden
    return exposed


# -- the device, from the traced slice ---------------------------------------------

# the solvers' jitted entry points are all called `run...` (ops/assign.py's
# warm twins, ops/auction.py, parallel/sharded.py)
SOLVE_PREFIX = "jit_run"


def trace_cycles(rec) -> list:
    tw = rec["trace_window"]
    if rec["trace"] is None or tw[0] is None:
        return []
    return [c for c in rec["cycles"] if tw[0] <= c.get("t_dispatch0", -1.0) and c["t_decode1"] <= tw[1]]


def device_idle_share(rec):
    """1 - (union of the device's operation intervals) over the traced slice
    of the window, in percent; None where there is no device trace."""
    tr = rec["trace"]
    if tr is None or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def solve_device_seconds(rec):
    """(device seconds, executions) of the solve programs in the traced
    slice, or None where there is no device trace."""
    tr = rec["trace"]
    if tr is None:
        return None
    hit = [v for k, v in tr["modules"].items() if k.startswith(SOLVE_PREFIX)]
    if not hit:
        return None
    return sum(v[0] for v in hit), sum(v[1] for v in hit)


def solve_roofline_share(rec):
    """Least seconds the traced cycles' solves could take, by their shapes,
    over the device seconds they took, in percent."""
    dev = solve_device_seconds(rec)
    cyc = [c for c in trace_cycles(rec) if "P" in c]
    if dev is None or not cyc or dev[0] <= 0:
        return None
    bw = peaks.peak(rec["device"]["kind"])["hbm_bytes_per_s"]
    # the solve programs run once a cycle; scale the traced executions' time
    # to the cycles whose shapes were recorded inside the slice
    least = sum(roofline.solve_min_seconds(c["P"], c["N"], c["R"], bw) for c in cyc)
    per_exec = dev[0] / max(dev[1], 1)
    return 100.0 * least / (per_exec * len(cyc))
