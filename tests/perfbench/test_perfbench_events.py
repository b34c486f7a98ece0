"""The two per-layer metrics that read span ``events.flush``
(``events_cpu_share.backlog`` / ``.steady``): the share on a toy record whose
spans are given, a finite share on traced toy runs of their cells through
``run_cell``, nothing for the plain reference (no recorder) and nothing for a
program whose recorder has no such span (a parent commit with these files laid
over it)."""

import json
import math
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import harness, programtrace  # noqa: E402
from perfbench import run as bench  # noqa: E402
from perfbench.manifest import Manifest  # noqa: E402

DOC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
FAST = {"replay_walk": [8], "replay_cap_s": 2.0, "drain_s": 6.0}
SPAN = "events.flush"
NEW = [(m["name"], m["workloads"][0]) for m in DOC["per_layer"]
       if m["name"].startswith("events_cpu_share.")]


def run(cell, system="served"):
    m = Manifest()
    return harness.run_cell(m, m.cell(cell), 2**31 + 30, 2.0, True, True, system_name=system,
                            t_start=time.perf_counter(), overrides=FAST)


@pytest.fixture(scope="module")
def served():
    return {cell: run(cell) for _, cell in NEW}


@pytest.fixture(scope="module")
def reference():
    return run("perf5k-basic-steady", system="reference")


def span(name, start, end, cpu0, cpu1):
    return {"name": name, "start": start, "end": end, "cpu0": cpu0, "cpu1": cpu1}


def test_the_two_metrics_are_listed_as_the_issue_names_them():
    mine = [m for m in DOC["per_layer"] if m["name"].startswith("events_cpu_share.")]
    assert sorted((m["name"], m["workloads"]) for m in mine) == [
        ("events_cpu_share.backlog", ["perf5k-basic-closed256", "perf5k-spread-closed256",
                                      "perf5k-antiaffinity-closed256-live2000"]),
        ("events_cpu_share.steady", ["perf5k-basic-steady"])]
    for m in mine:
        assert (m["unit"], m["better"], m["source"]) == ("%", "lower", "program_span")
        assert m["layer"] == "event recorder (client/events.py)"
        assert m["moves"] == ("bind_p50_s" if m["name"].endswith(".steady")
                              else "bound_pods_per_s")


@pytest.mark.parametrize("name,cell", NEW)
def test_the_share_is_the_flushes_cpu_over_the_seconds_between_the_edges(name, cell):
    read = Manifest().reader("per_layer", name)
    spans = [span(SPAN, 100.5, 100.9, 7.00, 7.25), span(SPAN, 103.0, 103.1, 7.25, 7.35),
             span("sched.encode", 101.0, 102.0, 3.0, 4.0),
             span(SPAN, 107.0, None, 7.35, None)]       # still open: left out
    rec = {"_programtrace": {"edges": (100.0, 110.0), "spans": spans}}
    assert read(rec) == pytest.approx(3.5)         # 0.35 s of CPU in a 10 s window
    rec["_programtrace"]["spans"] = spans[2:3]      # a program with a recorder and no such span
    assert read(rec) is None
    assert read({"_programtrace": None}) is None    # no recorder at all


@pytest.mark.parametrize("name,cell", NEW)
def test_the_reader_gives_a_share_on_a_traced_toy_run(served, name, cell):
    rec = served[cell]
    value = Manifest().reader("per_layer", name)(rec)
    assert value is not None and math.isfinite(value) and 0.0 <= value <= 100.0
    flushes = programtrace.spans_named(rec, (SPAN,))
    assert flushes and len({s["thread"] for s in flushes}) == 1     # the broadcaster's thread
    # one Scheduled event a bind: between the edges the flushes wrote about what was bound
    written = sum(s["n"] for s in flushes)
    assert written >= 1 and all(s["a0"] == 0 for s in flushes)     # nothing dropped at the cap
    sweeps = programtrace.spans_named(rec, ("events.expire",))
    assert all(s["n"] == 0 and s["a0"] == 0 for s in sweeps)       # nothing is an hour old


@pytest.mark.parametrize("name,cell", NEW)
def test_the_reader_gives_none_without_a_recorder(reference, name, cell):
    assert Manifest().reader("per_layer", name)(reference) is None


@pytest.mark.parametrize("name,cell", NEW)
def test_the_line_leaves_the_metric_out_where_the_program_has_no_such_span(served, name, cell):
    rec = dict(served[cell])
    pt = dict(programtrace.load(rec))
    pt["spans"] = [s for s in pt["spans"] if s["name"] != SPAN]    # the parent's recorder
    rec["_programtrace"] = pt
    assert Manifest().reader("per_layer", name)(rec) is None
    m = Manifest()
    line = bench.result_line(m, m.cell(cell), rec, True)
    assert name not in line["metrics"] and len(line["metrics"]) >= 10
    line = bench.result_line(m, m.cell(cell), served[cell], True)
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"][name]["unit"] == "%"
