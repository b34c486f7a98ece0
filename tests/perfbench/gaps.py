#!/usr/bin/env python3
"""A builder's tool: one traced run of a cell, read through the program's own
recorder (``kubernetes_tpu/utils/trace.py``).

    python3 tests/perfbench/gaps.py --workload <cell> --seed <n> [--seconds <s>]
        [--trace 0] [--rehearse-cpu] [--out FILE]

The benchmark's result line names the device's idle gaps by the harness's six
span names (``harness.SPAN_NAMES``).  ``tracered.reduce`` takes the names as an
argument, so this tool runs the same traced cell, keeps the profiler's planes
and reduces them a second time with the program's ``sched.*`` / ``store.*`` /
``gc`` spans: the finer names, with no edit to the benchmark.  Beside them it
prints, from the same run: the residual of the per-pod identity (issued to
seen = the six stages), the six stage medians beside the run's own end-to-end
latency, the off-CPU shares, the rows the rings dropped, the process's resident
size at the window's close, and every per-layer metric of the cell.  With
``--trace 0`` the run is the untraced one (no profiler, no gaps): the end-to-end
metrics beside the resident size and the device's peak, which is how the
recorder's cost is read against a parent checkout that has this file laid over.

The last line of standard output is one JSON object.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# the program's span names, a closed set (docs/scheduler_loop.md)
PROGRAM_SPANS = (
    "sched.pop_wait", "sched.encode", "sched.encode.lock_wait", "sched.dispatch",
    "sched.decode_wait", "sched.stage", "sched.wave_handoff", "sched.postfilter",
    "sched.commit", "sched.commit.pre_bind", "sched.commit.post_bind",
    "store.update_wave", "store.journal", "gc",
)   # store.create is tallied, not annotated: the harness's own store_create names that gap


def resident_bytes():
    """The process's resident size now, from /proc (None elsewhere)."""
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        return None


class ResidentSampler:
    """The process's resident size twice a second on the run's clock, so the
    reading nearest the window's close can be picked afterwards."""

    def __init__(self):
        import threading

        self.samples: list = []     # (perf_counter, bytes)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, name="gaps-rss", daemon=True)

    def _run(self) -> None:
        while not self._stop.wait(0.5):
            self.samples.append((time.perf_counter(), resident_bytes()))

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join(timeout=5.0)

    def at(self, t: float):
        near = min(self.samples, key=lambda s: abs(s[0] - t), default=None)
        return near[1] if near else None


def run(args) -> dict:
    from perfbench import harness, programtrace, reduce, tracered
    from perfbench import run as bench
    from perfbench.manifest import Manifest

    manifest = Manifest()
    cell = manifest.cell(args.workload)
    seconds = float(args.seconds if args.seconds is not None else manifest.run_seconds)
    bench.check_device(cell, args.rehearse_cpu)

    kept = {}
    inner_reduce = tracered.reduce

    def keeping_reduce(planes, span_names, *a, **kw):
        kept["planes"] = planes
        return inner_reduce(planes, span_names, *a, **kw)

    # the harness reduces the trace it took; keep the planes it loaded
    tracered.reduce = keeping_reduce
    try:
        with ResidentSampler() as rss:
            record = harness.run_cell(
                manifest, cell, args.seed, seconds, bool(args.trace), args.rehearse_cpu,
                t_start=T_START,
            )
    finally:
        tracered.reduce = inner_reduce

    out = {"cell": cell["name"], "seed": args.seed, "trace": args.trace,
           "device": record["device"], "setup_s": record["setup_s"],
           "correct": bool(record["verdict"]["correct"]),
           "resident_bytes_at_close": rss.at(record["t_close"]),
           "peak_device_bytes": record["peak_device_bytes"]}
    pt = programtrace.load(record)
    if pt is not None:
        paths = programtrace.paths(record)
        out["paths"] = len(paths)
        out["identity_residual_s"] = programtrace.residual(record)
        out["stage_p50_s"] = {s: programtrace.stage_p50(record, s) for s in programtrace.STAGES}
        out["issued_to_seen_p50_s"] = reduce.percentile(
            [p["seen"] - p["issued"] for p in paths], 50)
        out["dropped_spans"], out["dropped_pods"] = pt["dropped_spans"], pt["dropped_pods"]
        from kubernetes_tpu.utils import trace

        whole = trace.snapshot(record["t_start"])
        if whole is not None:       # how full the rings got: set-up, window, drain, comparison
            out["rows_whole_run"] = {"spans": len(whole["spans"]), "pods": len(whole["pods"])}
        by_name = {}
        for s in pt["spans"]:
            if s["end"] is None:
                continue
            if s["parent"] == trace.TALLIED:    # a sum of short intervals
                ent = by_name.setdefault(s["name"] + " (tallied)", [0, 0.0, None])
                ent[0] += s["n"]
                ent[1] += s["a0"]
                continue
            ent = by_name.setdefault(s["name"], [0, 0.0, None])
            ent[0] += 1
            ent[1] += s["end"] - s["start"]
            if s["cpu0"] is not None and s["cpu1"] is not None:    # read on this span
                ent[2] = (ent[2] or 0.0) + s["cpu1"] - s["cpu0"]
        out["spans_between_edges"] = {
            k: {"count": v[0], "wall_s": v[1], "cpu_s": v[2]} for k, v in sorted(by_name.items())
        }
    planes = kept.get("planes")
    if planes is not None:
        fine = tracered.reduce(planes, PROGRAM_SPANS + ("perfbench_window",))
        if fine is not None:
            out["idle_gaps_by_program_span"] = fine["idle_gaps"]
            out["idle_gaps_by_harness_span"] = (record["trace"] or {}).get("idle_gaps")
    if args.trace:
        out["per_layer"] = {
            k: v["value"]
            for k, v in bench.read_metrics(manifest, cell["name"], "per_layer", record).items()
        }
    out["end_to_end"] = {
        k: v["value"] for k, v in bench.read_metrics(manifest, cell["name"], "end_to_end", record).items()
    }
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    out = run(args)
    if out.get("identity_residual_s") is not None:
        print(f"gaps: identity residual {out['identity_residual_s']:.3e} s over "
              f"{out['paths']} bound pods", file=sys.stderr)
    line = json.dumps(out)
    if args.out:
        path = os.path.abspath(args.out)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
