"""The one command at toy size on the CPU platform: every cell end to end,
the contract's last line, the device named in it, no device metric in it;
and a configuration, a traffic mix and a per-layer metric added as files
and entries only."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DOC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
DEVICE_SOURCES = {"device_trace"}


def run(root, cell, trace, extra=()):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT, BENCH_RUN="7")
    cmd = [sys.executable, os.path.join(root, "perfbench", "run.py"), "--workload", cell,
           "--seed", str(2**31 + 12345), "--seconds", "3", "--trace", str(trace), *extra]
    return subprocess.run(cmd, cwd=root, env=env, capture_output=True, text=True, timeout=600)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"       # the numbers compared come last
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    return line


@pytest.mark.parametrize("cell", [w["name"] for w in DOC["workloads"]])
def test_toy_rehearsal_runs_the_cell_end_to_end(cell):
    proc = run(ROOT, cell, 1, ["--rehearse-cpu"])
    line = last_line(proc)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] >= 1
    by_name = {m["name"]: m for m in DOC["per_layer"]}
    assert line["metrics"], "a traced run reports per-layer metrics"
    for name in line["metrics"]:
        # a number from a CPU run is never written under a device metric's name
        assert by_name[name]["source"] not in DEVICE_SOURCES, name
    assert "busy_s" not in line["device"] and "breakdown" not in line
    for name, (value, limit) in line["checks"].items():
        assert value <= limit, name
    # the same numbers end standard error
    assert "perfbench: correct = True" in proc.stderr.strip().splitlines()[-1]


def test_end_to_end_line_and_no_tpu_no_result():
    cell = DOC["workloads"][0]["name"]
    line = last_line(run(ROOT, cell, 0, ["--rehearse-cpu"]))
    e2e = {m["name"] for m in DOC["end_to_end"] if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) == e2e and all(v["value"] > 0 for v in line["metrics"].values())
    # without --rehearse-cpu the command wants a TPU and prints no result
    proc = run(ROOT, cell, 0)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_a_later_pr_adds_a_config_a_mix_and_a_metric_as_files_only(tmp_path):
    root = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(ROOT, "perfbench"), os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {}
    for d, _, files in os.walk(os.path.join(root, "perfbench")):
        for f in files:
            p = os.path.join(d, f)
            before[p] = open(p, "rb").read()

    # a configuration: the basic deployment with another number of init pods
    cfg = json.load(open(os.path.join(root, "perfbench/configs/sched-perf-5000n.json")))
    cfg["name"] = "sched-perf-later"
    cfg["toy"]["initPods"] = 8
    json.dump(cfg, open(os.path.join(root, "perfbench/configs/sched-perf-later.json"), "w"))
    # a traffic mix: the open loop at another rate
    mix = json.load(open(os.path.join(root, "perfbench/traffic/steady.json")))
    mix["toy"]["rate_pods_per_s"] = 25.0
    json.dump(mix, open(os.path.join(root, "perfbench/traffic/trickle.json"), "w"))
    # a per-layer metric: a reader of its own over the run's record
    with open(os.path.join(root, "perfbench/metrics/pods_due.trickle.py"), "w") as f:
        f.write("def read(rec):\n    return float(len(rec['due']))\n")

    doc = json.loads(json.dumps(DOC))
    doc["configs"].append({"name": "sched-perf-later", "source": cfg["source"],
                           "file": "perfbench/configs/sched-perf-later.json",
                           "reduced": ["measurePods"], "why": "added by the test"})
    doc["workloads"].append({"name": "later-trickle", "config": "sched-perf-later",
                             "traffic": "trickle", "chips": 1, "why": "added by the test"})
    for m in doc["end_to_end"]:
        if m["name"] == "bind_p50_s":
            m["workloads"].append("later-trickle")
    doc["per_layer"].append({"name": "pods_due.trickle", "unit": "pods", "better": "higher",
                             "source": "program_counter", "layer": "generator (benchmark)",
                             "moves": "bind_p50_s", "workloads": ["later-trickle"]})
    json.dump(doc, open(os.path.join(root, "BENCHMARK.json"), "w"))

    line = last_line(run(root, "later-trickle", 1, ["--rehearse-cpu"]))
    assert line["correct"] is True
    assert line["metrics"]["pods_due.trickle"]["value"] == 75.0      # 25 pods/s for 3 s
    line = last_line(run(root, "later-trickle", 0, ["--rehearse-cpu"]))
    assert set(line["metrics"]) == {"bind_p50_s", "setup_s"}
    # no file that was there has been edited
    for p, data in before.items():
        assert open(p, "rb").read() == data, p
