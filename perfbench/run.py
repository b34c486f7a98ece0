#!/usr/bin/env python3
"""The benchmark's one command.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

runs one cell of BENCHMARK.json on the machine it is started on and prints, as
the last line of standard output, one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with ``--trace 1``
its per-layer metrics), ``device`` and, last, ``checks``: every number the
comparison held to a limit, beside that limit.  The same numbers are the last
lines of standard error.

It exits with a code other than 0, and prints no result, where JAX finds no
TPU or fewer chips than the cell asks for.  ``--rehearse-cpu`` runs the same
code at toy size on the CPU platform as a pre-flight: the line names the
device, and no device metric is in it.

The builder's trials (the plain reference or a control in the program's place,
the rate sweep, a parameter overridden, a run's details written to a file) are
not options of this command: ``tests/perfbench/builder.py`` drives the same
functions.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    return ap.parse_args(argv)


def check_device(cell: dict, rehearse: bool) -> dict:
    from perfbench import harness

    device = harness.device_info()
    if rehearse:
        if device["platform"] != "cpu":
            raise SystemExit("perfbench: --rehearse-cpu is for a CPU-only host")
        print(
            "perfbench: REHEARSAL on the CPU platform at toy size: not a chip result; "
            "device metrics are not measured", file=sys.stderr,
        )
        return device
    if device["platform"] != "tpu":
        raise SystemExit(f"perfbench: JAX found no TPU (devices: {device}); refusing to run")
    if device["count"] < int(cell["chips"]):
        raise SystemExit(
            f"perfbench: the cell asks for {cell['chips']} chip(s), JAX found {device['count']}"
        )
    return device


def read_metrics(manifest, cell_name: str, group: str, record: dict) -> dict:
    out = {}
    for m in manifest.metrics_for(cell_name, group):
        value = manifest.reader(group, m["name"])(record)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(manifest, cell: dict, record: dict, trace: bool) -> dict:
    group = "per_layer" if trace else "end_to_end"
    metrics = read_metrics(manifest, cell["name"], group, record)
    device = dict(record["device"])
    device["memory_peak_bytes"] = record["peak_device_bytes"]
    line = {}
    tr = record["trace"]
    if trace and tr is not None:
        device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
        line["breakdown"] = {"device_ops": tr["device_ops"], "idle_gaps": tr["idle_gaps"]}
    verdict = record["verdict"]
    if record["kind"] == "backlog":
        attempted = sum(1 for c in record["created"] if c[3] >= record["t_open"])
        failed = sum(
            1 for c in record["created"]
            if c[3] >= record["t_open"] and (c[0], c[1]) not in record["bound"]
        )
    else:
        attempted = len(record["due"])
        failed = sum(1 for c in record["due"] if (c[0], c[1]) not in record["bound"])
    return {
        "correct": bool(verdict["correct"]),
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": device,
        **line,
        "checks": verdict["checks"],
    }


def details(record: dict) -> dict:
    """What a run learned beyond its last line: set-up phases, what compiled
    and when, the waves, the cycles."""
    from perfbench import reduce

    comp = {}
    for t, kind, fun, secs in record["compiles"]:
        if kind != "backend_compile":
            continue
        when = (
            "window" if record["t_open"] <= t < record["t_close"]
            else "warm_replay" if record["replay"]["t0"] <= t < record["t_open"]
            else "after_window" if t >= record["t_close"]
            else "warmup_and_before" if t < record["t_warmup_end"] else "init_pods_and_walk"
        )
        ent = comp.setdefault(when, {}).setdefault(fun, [0, 0.0])
        ent[0] += 1
        ent[1] += secs
    inside = [w for w in reduce.waves_of(record)
              if record["t_open"] <= w.t_first < record["t_close"]]
    cyc = reduce.cycles(record)
    return {
        "setup_phases": record["setup_phases"], "replay": record["replay"],
        "compiles_by_phase": comp,
        "waves_in_window": [[w.t_first - record["t_open"], w.t_last - w.t_first, w.pods] for w in inside],
        "cycles_in_window": len(cyc),
        "routes": sorted({str(c.get("route")) for c in cyc}),
        "shapes": sorted({(c.get("P"), c.get("N"), c.get("R")) for c in cyc}, key=str),
        "client": record["client"], "compare_s": record["compare_s"],
        "drained": record["drained"], "drain_s": record["t_drained"] - record["t_close"],
        "drain_after_load_off_s": record["t_drained"] - record["t_off"],
        "gc_in_window": {
            str(gen): [
                sum(1 for p in record["gc_pauses"]
                    if p[2] == gen and record["t_open"] <= p[0] < record["t_close"]),
                reduce.gc_seconds_between(record, record["t_open"], record["t_close"], gen),
            ] for gen in (0, 1, 2)
        },
        "deleted": len(record["deleted"]), "pending_at_end": record["pending_at_end"],
        "live_in_window": reduce.live_range(record, record["t_open"], record["t_close"]),
        "pods_bound_at_open": sum(1 for b in record["bind_log"] if b[0] < record["t_open"]),
        "counters_open": record["counters_open"], "counters_close": record["counters_close"],
        "trace_modules": (record["trace"] or {}).get("modules"),
        "trace_lines": (record["trace"] or {}).get("lines"),
        "bind_times": [b[0] - record["t_open"] for b in record["bind_log"]],
        "cycles": [
            {k: (v - record["t_open"] if k.startswith("t_") else v) for k, v in c.items()
             if k != "keys"}
            for c in cyc
        ],
    }


LONG_DETAILS = ("waves_in_window", "counters_open", "counters_close", "bind_times", "cycles",
                "trace_lines")


def report(line: dict, extra: dict) -> None:
    """The run's details on standard error, the result as the last line of
    standard output, and every number compared beside its limit as the last
    lines of standard error."""
    print("perfbench: details " + json.dumps(
        {k: v for k, v in extra.items() if k not in LONG_DETAILS}
    ), file=sys.stderr)
    print(json.dumps(line))
    sys.stdout.flush()
    for name, (value, limit) in line["checks"].items():
        print(f"perfbench: check {name} = {value} (limit {limit})", file=sys.stderr)
    print(f"perfbench: correct = {line['correct']}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    args = parse(argv)
    from perfbench.manifest import Manifest

    manifest = Manifest()
    cell = manifest.cell(args.workload)
    seconds = float(args.seconds if args.seconds is not None else manifest.run_seconds)
    check_device(cell, args.rehearse_cpu)

    from perfbench import harness

    record = harness.run_cell(
        manifest, cell, args.seed, seconds, bool(args.trace), args.rehearse_cpu,
        t_start=T_START,
    )
    report(result_line(manifest, cell, record, bool(args.trace)), details(record))
    return 0


if __name__ == "__main__":
    sys.exit(main())
