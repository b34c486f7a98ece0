#!/usr/bin/env python3
"""The builder's trials, kept off the benchmark's one command.

    python3 tests/perfbench/builder.py --workload <cell> --seed <n> --seconds <s>
        [--trace 1] [--rehearse-cpu] [--dump FILE]
        [--system reference [--control NAME[,NAME]]]
        [--set KEY=JSON ...] [--gc-freeze] [--sweep r1,r2,... [--hold SECONDS]]
        [--as-cell LISTED_CELL]

drives the functions ``perfbench/run.py`` drives, with what a cell's proof
needs besides: the plain reference (whole, or with one guarantee broken: the
control, which is a rule file's own (`capacity`, `skew`, `antiaffinity`), the
store's (`durability`, `delete_durability`, `delete_lost`, `once`) or, where a role is not meant to
bind, `unplaceable`) in the program's place, a parameter of the traffic file overridden, a
run's details written to a file, ``gc.freeze()`` after set-up (an experiment
on the program's behalf that the benchmark itself never makes), and the rate
sweep that finds an open loop's knee.  ``--workload`` may also be ``<configuration>:<mix>`` for a cell that
BENCHMARK.json does not list yet; no metric lists such a cell, so ``--as-cell`` names a listed one whose
metrics' readers are run over its record (a hand run's numbers, under no cell's name in any ledger).
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
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--system", choices=("served", "reference"), default="served")
    ap.add_argument("--control", default=None)
    ap.add_argument("--set", action="append", default=[], metavar="KEY=JSON")
    ap.add_argument("--dump", default=None)
    ap.add_argument("--gc-freeze", action="store_true")
    ap.add_argument("--sweep", default=None, help="comma-separated rates, pods/s")
    ap.add_argument("--hold", type=float, default=10.0, help="seconds per sweep step")
    ap.add_argument("--as-cell", default=None, help="read this listed cell's metrics")
    args = ap.parse_args(argv)

    from perfbench import harness
    from perfbench import run as bench
    from perfbench.manifest import Manifest

    manifest = Manifest()
    if ":" in args.workload:
        config, mix = args.workload.split(":", 1)
        cell = {"name": args.workload, "config": config, "traffic": mix, "chips": 1}
    else:
        cell = manifest.cell(args.workload)
    seconds = float(args.seconds if args.seconds is not None else manifest.run_seconds)
    bench.check_device(cell, args.rehearse_cpu)

    if args.sweep:
        import sweep

        rates = [float(r) for r in args.sweep.split(",")]
        table = sweep.run(manifest, cell, args.seed, rates, args.hold, args.rehearse_cpu)
        print(json.dumps({"sweep": table}))
        return 0

    def freeze(system):      # after nodes, warmup, init pods and the walk; before the replay
        import gc

        gc.collect()
        gc.freeze()

    record = harness.run_cell(
        manifest, cell, args.seed, seconds, bool(args.trace), args.rehearse_cpu,
        system_name=args.system, control=args.control, t_start=T_START,
        plant=freeze if args.gc_freeze else None,
        overrides={k: json.loads(v) for k, v in (kv.split("=", 1) for kv in args.set)},
    )
    read_as = manifest.cell(args.as_cell) if args.as_cell else cell
    if read_as["name"] in {w["name"] for w in manifest.doc["workloads"]}:
        line = bench.result_line(manifest, read_as, record, bool(args.trace))
    else:       # no metrics are listed for it yet: the comparison alone
        line = {"correct": bool(record["verdict"]["correct"]), "setup_s": record["setup_s"],
                "checks": record["verdict"]["checks"]}
    extra = bench.details(record)
    if args.dump:
        os.makedirs(os.path.dirname(os.path.abspath(args.dump)), exist_ok=True)
        with open(args.dump, "w") as f:
            both = {g: bench.read_metrics(manifest, read_as["name"], g, record)
                    for g in ("end_to_end", "per_layer")}
            json.dump({"result": line, **both, "details": extra}, f)
    bench.report(line, extra)
    return 0


if __name__ == "__main__":
    sys.exit(main())
