"""The two per-layer metrics that read span ``sched.encode.constraints``
(``encode_bound_entries_read_per_cycle.steady`` / ``.backlog``) on toy traced
runs of their cells through ``run_cell``: a finite number for the served
program (0: the mixes bring no constraint row and no bound pod owns a term),
nothing for the plain reference (no recorder) and nothing for a program
whose recorder has no such span (a parent commit with these files laid over
it)."""

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
# under the suite's load a toy run builds its executables slowly: the replay may
# wait for them and the drain for the closing wave (as in the two modules beside)
FAST = {"replay_walk": [8], "replay_cap_s": 20.0, "drain_s": 30.0}
SPAN = "sched.encode.constraints"
NEW = [(m["name"], m["workloads"][0]) for m in DOC["per_layer"]
       if m["name"].startswith("encode_bound_entries_read_per_cycle.")]


def run(cell, system="served"):
    m = Manifest()
    # a 2 s window that held no whole wave (a build inside it): the next run finds it built
    for _ in range(3):
        rec = harness.run_cell(m, m.cell(cell), 2**31 + 26, 2.0, True, True, system_name=system,
                               t_start=time.perf_counter(), overrides=FAST)
        if reduce.edges(rec) is not None:
            break
    programtrace.load(rec)      # at once: the next run in this process reuses pod keys
    return rec


@pytest.fixture(scope="module")
def served():
    return {cell: run(cell) for _, cell in NEW}


@pytest.fixture(scope="module")
def reference():
    return run("perf5k-basic-steady", system="reference")


def test_the_two_metrics_are_listed_as_the_issue_names_them():
    assert sorted(NEW) == [
        ("encode_bound_entries_read_per_cycle.backlog", "perf5k-basic-closed256"),
        ("encode_bound_entries_read_per_cycle.steady", "perf5k-basic-steady")]
    for m in DOC["per_layer"]:
        if m["name"].startswith("encode_bound_entries_read_per_cycle."):
            assert (m["unit"], m["better"], m["source"]) == ("entries", "lower", "program_span")
            assert m["layer"] == "snapshot encode (ops/schema.py, encode_pending)"
            assert m["moves"] == ("bind_p50_s" if m["name"].endswith(".steady")
                                  else "bound_pods_per_s")


@pytest.mark.parametrize("name,cell", NEW)
def test_the_reader_gives_zero_on_a_traced_toy_run_of_a_constraint_free_mix(served, name, cell):
    value = Manifest().reader("per_layer", name)(served[cell])
    assert value is not None and math.isfinite(value)
    assert value == 0.0


@pytest.mark.parametrize("cell", [c for _, c in NEW])
def test_every_encode_has_one_constraints_span_inside_it_with_the_index_size(served, cell):
    rec = served[cell]
    spans = programtrace.spans_named(rec, (SPAN,))
    encodes = {s["id"]: s for s in programtrace.spans_named(rec, ("sched.encode",))}
    assert spans and len(spans) == len({s["parent"] for s in spans})
    for s in spans:
        enc = encodes.get(s["parent"])
        if enc is None:
            continue        # its encode started before the first edge
        assert enc["start"] <= s["start"] and s["end"] <= enc["end"]
        assert s["thread"] == enc["thread"] and s["cycle"] == enc["cycle"]
    # a0: bound and assumed pods in the index; a1: their live signatures
    assert all(s["a0"] >= 1 and 1 <= s["a1"] <= s["a0"] for s in spans)
    assert spans[-1]["a0"] >= spans[0]["a0"]       # the cluster fills as the run goes


@pytest.mark.parametrize("name,cell", NEW)
def test_the_reader_gives_none_without_a_recorder(reference, name, cell):
    assert Manifest().reader("per_layer", name)(reference) is None


@pytest.mark.parametrize("name,cell", NEW)
def test_the_reader_gives_none_where_the_program_has_no_such_span(served, name, cell):
    rec = dict(served[cell])
    pt = dict(programtrace.load(rec))
    pt["spans"] = [s for s in pt["spans"] if s["name"] != SPAN]    # the parent's recorder
    rec["_programtrace"] = pt
    assert Manifest().reader("per_layer", name)(rec) is None
    m = Manifest()
    line = bench.result_line(m, m.cell(cell), rec, True)
    # the line is still printed, with the accepted metrics and without this one
    assert name not in line["metrics"] and len(line["metrics"]) >= 10


@pytest.mark.parametrize("name,cell", NEW)
def test_the_traced_line_carries_the_metric(served, name, cell):
    m = Manifest()
    line = bench.result_line(m, m.cell(cell), served[cell], True)
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"][name] == {"value": 0.0, "unit": "entries"}
