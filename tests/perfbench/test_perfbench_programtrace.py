"""The per-layer metrics that read the program's own recorder
(``perfbench/programtrace.py``), on toy traced runs of both cells through
``run_cell``: the per-pod identity, the order of the stamps, the span tree,
and every new reader: a number for the served program, None for the plain
reference (which has no recorder, as a parent commit has none)."""

import json
import math
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import harness, programtrace, reduce  # noqa: E402
from perfbench import run as bench  # noqa: E402
from perfbench.manifest import Manifest  # noqa: E402

DOC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
# a toy run under the suite's load (six workers on shared cores) builds its
# executables slowly: the replay may wait for them (the cap only bounds it) and
# the drain for the last bind, so that no build lies in the 2 s window
STEADY = {"replay_walk": [8], "replay_cap_s": 20.0, "drain_s": 30.0}
CELLS = ["perf5k-basic-steady", "perf5k-basic-closed256"]
PREFIXES = ("stage_", "encode_lock_wait_", "sched_thread_offcpu_", "commit_thread_offcpu_",
            "solve_route_")
NEW = [(m["name"], m["workloads"][0]) for m in DOC["per_layer"]
       if m["name"].startswith(PREFIXES)]


def run(cell, system="served"):
    """One toy record, whole: what the program's recorder holds of the run is
    read here, at once.  The next run in this process reuses pod keys (the
    same names in the same namespaces), and a snapshot taken later would join
    this run's pods to that run's rows."""
    m = Manifest()
    # a 2 s window that held no whole wave (a build inside it): the next run finds it built
    for _ in range(3):
        rec = harness.run_cell(m, m.cell(cell), 2**31 + 25, 2.0, True, True, system_name=system,
                               t_start=time.perf_counter(), overrides=STEADY)
        if reduce.edges(rec) is not None:
            break
    programtrace.load(rec)
    from kubernetes_tpu.utils import trace

    rec["_whole_trace"] = trace.snapshot(rec["t_start"], float("inf"))
    return rec


@pytest.fixture(scope="module")
def records():
    """The module's toy records, made once and read by every case."""
    out = {cell: run(cell) for cell in CELLS}
    out["reference"] = run("perf5k-basic-steady", system="reference")
    return out


@pytest.fixture(scope="module")
def served(records):
    return {cell: records[cell] for cell in CELLS}


@pytest.fixture(scope="module")
def reference(records):
    return records["reference"]


def test_nineteen_new_metrics_are_listed_with_a_cell_each():
    assert len(NEW) == 19 and {c for _, c in NEW} == set(CELLS)


@pytest.mark.parametrize("cell", CELLS)
def test_issued_to_seen_is_the_six_stages_to_the_float(served, cell):
    rec = served[cell]
    paths = programtrace.paths(rec)
    bound = [k for k, _ in programtrace.population(rec) if k in rec["bound"]]
    assert paths and len(paths) == len(bound)          # every bound pod has a whole path
    for p in paths:
        total = sum(p[s] for s in programtrace.STAGES)
        assert abs((p["seen"] - p["issued"]) - total) < 1e-9, p["key"]
    assert programtrace.residual(rec) < 1e-9


@pytest.mark.parametrize("cell", CELLS)
def test_stamps_are_in_order_and_the_cycle_holds_popped_to_solved(served, cell):
    rec = served[cell]
    pt = programtrace.load(rec)
    from kubernetes_tpu.utils import trace

    whole = rec["_whole_trace"]
    cycles = {s[0]: dict(zip(trace.SPAN_FIELDS, s)) for s in whole["spans"]
              if s[1] == "sched.cycle"}
    assert pt["dropped_spans"] == 0 and pt["dropped_pods"] == 0
    for p in programtrace.paths(rec):
        row = p["row"]
        marks = [row[k] for k in ("enqueued", "popped", "solved", "commit_begin", "committed")]
        assert marks == sorted(marks), p["key"]
        assert row["attempts"] >= 1 and row["failed"] is None and row["route"] >= 0
        cyc = cycles[row["cycle"]]
        assert cyc["start"] <= row["popped"] and row["solved"] <= cyc["end"], p["key"]
        assert cyc["n"] >= 1


@pytest.mark.parametrize("cell", CELLS)
def test_children_lie_inside_parents_and_a_cycles_children_do_not_overlap(served, cell):
    rec = served[cell]
    from kubernetes_tpu.utils import trace

    rows = [dict(zip(trace.SPAN_FIELDS, s)) for s in rec["_whole_trace"]["spans"]]
    by_id = {r["id"]: r for r in rows}
    closed = [r for r in rows if r["end"] is not None]
    assert {r["name"] for r in closed} >= {
        "sched.cycle", "sched.pop_wait", "sched.encode", "sched.encode.lock_wait",
        "sched.dispatch", "sched.decode_wait", "sched.stage", "sched.wave_handoff",
        "sched.postfilter", "sched.commit", "sched.commit.pre_bind", "sched.commit.post_bind",
        "store.update_wave", "store.create", "store.journal", "gc"}
    for r in closed:
        parent = by_id.get(r["parent"])
        if parent is None or parent["end"] is None or r["name"] == "gc":
            continue
        if parent["thread"] == r["thread"]:
            assert parent["start"] <= r["start"] and r["end"] <= parent["end"], r
        else:
            # a worker's span under the cycle that staged its wave
            assert r["name"] == "sched.commit" and parent["name"] == "sched.cycle"
            assert r["start"] >= parent["start"]
    for cyc in (r for r in closed if r["name"] == "sched.cycle"):
        kids = sorted((r for r in closed if r["parent"] == cyc["id"] and r["name"] != "gc"
                       and r["thread"] == cyc["thread"]),
                      key=lambda r: r["start"])
        assert kids
        for a, b in zip(kids, kids[1:]):
            assert a["end"] <= b["start"], (a, b)
        # the pop that brought the batch belongs to no cycle: it ends where
        # this one starts, by the same clock read, on the same thread
        assert any(r["name"] == "sched.pop_wait" and r["end"] == cyc["start"]
                   and r["thread"] == cyc["thread"] and r["cycle"] == r["parent"] == 0
                   for r in closed)
    # a commit's parts are its children, the store's transaction under it
    for r in closed:
        if r["name"] == "store.update_wave" and r["parent"] in by_id:
            assert by_id[r["parent"]]["name"] == "sched.commit"
        if r["name"] == "store.journal" and r["parent"] in by_id:
            assert by_id[r["parent"]]["name"] == "store.update_wave"
        if r["name"] == "store.create":
            # a client's writes are summed, a row a thread and tenth of a second
            assert r["parent"] == trace.TALLIED and r["n"] >= 1
            assert 0.0 < r["a0"] <= r["end"] - r["start"] + 1e-9


@pytest.mark.parametrize("name,cell", NEW)
def test_each_new_reader_gives_a_number_on_a_traced_toy_run(served, name, cell):
    value = Manifest().reader("per_layer", name)(served[cell])
    assert value is not None and math.isfinite(value)
    if name.startswith(("sched_thread_offcpu", "commit_thread_offcpu")):
        assert 0.0 <= value <= 100.0
    if name.startswith("solve_route"):
        # a count of pods by route.  Which route a toy batch takes follows its
        # size and that the machine's load, so the share itself may be anything;
        # but it is the named route's, as the harness's own record of the
        # solves has it, and the routes' shares make up the whole
        rec, route = served[cell], name.split("_")[2]
        pt = programtrace.load(rec)
        assert route in pt["routes"]
        shares = [programtrace.route_pods_share(rec, r) for r in pt["routes"]]
        assert sum(shares) == pytest.approx(100.0)
        routes_of = {}
        for c in rec["cycles"]:
            for key in c.get("keys", ()):
                routes_of.setdefault(key, set()).add(c["route"])
        pods = [k for k, _ in programtrace.population(rec)
                if k in pt["pods"] and pt["pods"][k]["route"] >= 0]
        assert pods and all(k in routes_of for k in pods)
        sure = sum(1 for k in pods if routes_of[k] == {route})
        retried = sum(1 for k in pods if route in routes_of[k] and len(routes_of[k]) > 1)
        assert 100.0 * sure / len(pods) - 1e-9 <= value
        assert value <= 100.0 * (sure + retried) / len(pods) + 1e-9


@pytest.mark.parametrize("name,cell", NEW)
def test_each_new_reader_gives_none_without_a_recorder(reference, name, cell):
    assert Manifest().reader("per_layer", name)(reference) is None


@pytest.mark.parametrize("cell", CELLS)
def test_the_traced_line_carries_the_new_metrics_beside_the_old(served, cell):
    m = Manifest()
    line = bench.result_line(m, m.cell(cell), served[cell], True)
    assert line["correct"] is True and line["failed"] == 0
    want = {n for n, c in NEW if c == cell}
    assert want <= set(line["metrics"])
    old = {x["name"] for x in m.metrics_for(cell, "per_layer")
           if not x["name"].startswith(PREFIXES) and x["source"] != "device_trace"
           and x["name"] != "peak_device_bytes"}
    assert old <= set(line["metrics"])


def test_a_program_without_the_recorder_reads_none_and_does_not_raise(served, monkeypatch):
    from kubernetes_tpu.utils import trace

    rec = dict(served["perf5k-basic-steady"])
    rec.pop("_programtrace", None)
    monkeypatch.delattr(trace, "snapshot")      # the parent commit's module has none
    assert programtrace.load(rec) is None
    assert programtrace.stage_p50(rec, "solve") is None
    assert programtrace.offcpu_share(rec, programtrace.LANE_SPANS) is None
    assert programtrace.lock_wait_ms_per_cycle(rec) is None
    assert programtrace.route_pods_share(rec, "greedy") is None
