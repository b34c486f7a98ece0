"""From a profiler trace to numbers: device busy time, time per XLA module
and per operation, and the longest idle gaps named by the host span that
covered them.

The reduction works on a plain form of the trace,
``{plane: {line: [(name, start_ns, duration_ns), ...]}}``, so that it can be
checked on a small recorded trace kept with the tests.  ``load_xplane`` makes
that form from the ``.xplane.pb`` the JAX profiler writes.
"""

from __future__ import annotations

import glob
import os

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load_xplane(trace_dir: str) -> dict:
    import jax.profiler

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    planes = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events
            )
    return planes


def union(intervals) -> list:
    """Disjoint, sorted cover of [(start, end), ...]."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def device_planes(planes: dict) -> list:
    return sorted(
        p for p in planes
        if p.startswith("/device:") and not p.startswith("/device:CUSTOM")
        and OPS_LINE in planes[p]
    )


def host_spans(planes: dict, names) -> list:
    """(name, start, end) of the harness's annotations, from every host
    thread."""
    names = set(names)
    out = []
    for pname, lines in planes.items():
        if not pname.startswith("/host:"):
            continue
        for events in lines.values():
            out.extend((n, s, s + d) for n, s, d in events if n in names)
    return sorted(out, key=lambda x: x[1])


def module_name(raw: str) -> str:
    """"jit_run(123456789)" -> "jit_run"."""
    return raw.split("(", 1)[0]


def op_name(raw: str) -> str:
    """The trace names an operation by its whole HLO line,
    "%while.15 = (s32[] ...) while(...)"; keep "while.15"."""
    return raw.split(" = ", 1)[0].lstrip("%")[:80]


def reduce(planes: dict, span_names, window_ns=None, top: int = 10):
    """None where the trace holds no device plane (a CPU run: not measured).
    `window_ns`: (start, end) on the trace's clock; default the extent of the
    harness's spans, else of the device's operations."""
    devs = device_planes(planes)
    if not devs:
        return None
    spans = host_spans(planes, span_names)
    if window_ns is None:
        marks = [(s, e) for n, s, e in spans if n == "perfbench_window"]
        if marks:
            window_ns = (min(s for s, _ in marks), max(e for _, e in marks))
        else:
            every = [(s, s + d) for p in devs for _, s, d in planes[p][OPS_LINE]]
            window_ns = (min(s for s, _ in every), max(e for _, e in every))
    w0, w1 = window_ns

    def clip(s, d):
        return max(s, w0), min(s + d, w1)

    busy, modules, ops = [], {}, {}
    per_dev_union = []
    for p in devs:
        ivs = []
        for name, s, d in planes[p][OPS_LINE]:
            a, b = clip(s, d)
            if b > a:
                ivs.append((a, b))
                key = op_name(name)
                ops[key] = ops.get(key, 0.0) + (b - a)
        u = union(ivs)
        per_dev_union.append(u)
        busy.append(sum(e - s for s, e in u))
        for name, s, d in planes[p].get(MODULES_LINE, ()):
            a, b = clip(s, d)
            if b > a:
                key = module_name(name)
                ent = modules.setdefault(key, [0.0, 0])
                ent[0] += b - a
                ent[1] += 1
    n = len(devs)
    # idle gaps of the first device (one chip: the device), named by the
    # host span that covers most of each
    gaps, edge = [], w0
    for s, e in per_dev_union[0] + [(w1, w1)]:
        if s > edge:
            gaps.append((edge, s))
        edge = max(edge, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for g0, g1 in gaps[:top]:
        best, best_cov = "none", 0.0
        for name, s, e in spans:
            if name == "perfbench_window":
                continue
            cov = min(e, g1) - max(s, g0)
            if cov > best_cov:
                best, best_cov = name, cov
        named.append([best, (g1 - g0) / 1e9])
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "devices": n,
        "modules": {k: [v[0] / n / 1e9, v[1]] for k, v in modules.items()},
        "device_ops": [
            [k, v / n / 1e9] for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:top]
        ],
        "idle_gaps": named,
        "lines": {p: {ln: len(ev) for ln, ev in planes[p].items()} for p in planes},
    }
