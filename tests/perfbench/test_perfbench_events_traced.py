"""The cases of ``test_perfbench_events.py`` that read a traced toy run of
``events_cpu_share.backlog`` / ``.steady``, on a toy run that holds a whole
wave.  Under six workers a build can fall inside the toy's 2 s window, and
``reduce.edges`` then finds no wave: the run is made again, as
``test_perfbench_programtrace.py`` does, and its recorder rows are read at
once.  ``pytest.ini`` deselects the same cases in ``test_perfbench_events.py``;
what each asserts is unchanged."""

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
# wait for them (the cap only bounds it) and the drain for the last bind
FAST = {"replay_walk": [8], "replay_cap_s": 20.0, "drain_s": 30.0}
SPAN = "events.flush"
NEW = [(m["name"], m["workloads"][0]) for m in DOC["per_layer"]
       if m["name"].startswith("events_cpu_share.")]


def run(cell, system="served"):
    """One toy record whose recorder rows are read at once: the next run in
    this process reuses pod keys, and the rings move on."""
    m = Manifest()
    # a 2 s window that held no whole wave (a build inside it): the next run finds it built
    for _ in range(3):
        rec = harness.run_cell(m, m.cell(cell), 2**31 + 30, 2.0, True, True, system_name=system,
                               t_start=time.perf_counter(), overrides=FAST)
        if reduce.edges(rec) is not None:
            break
    programtrace.load(rec)
    return rec


@pytest.fixture(scope="module")
def served():
    return {cell: run(cell) for _, cell in NEW}


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
