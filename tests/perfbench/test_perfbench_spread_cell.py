"""The cell ``perf5k-spread-closed256`` as BENCHMARK.json lists it: the
entries say what ``test_perfbench_correct.py`` spelled out by hand while the
configuration had no cell, the configuration's entry names the file that
``Manifest.config`` used to find by its name, and every per-layer metric the
cell lists has a reader that finds a number in a toy run's record."""

import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

from perfbench import harness  # noqa: E402
from perfbench.manifest import Manifest  # noqa: E402

import test_perfbench_correct as by_hand  # noqa: E402

CELL = "perf5k-spread-closed256"
CONFIG = "sched-perf-5000n-spread"
# what only a chip holds (its profiler trace, its memory statistics): no CPU run reads these
DEVICE_ONLY = {"solve_device_us_per_pod.backlog", "device_idle_share.backlog",
               "peak_device_bytes"}
NEW = {"constraint_encode_ms_per_cycle.backlog", "spread_rows_per_cycle.backlog",
       "solve_waves_per_cycle.backlog", "solve_wave_fallbacks_per_cycle.backlog"}


def test_the_cell_is_what_was_spelled_out_by_hand():
    m = Manifest()
    cell = m.cell(CELL)
    assert {k: cell[k] for k in by_hand.SPREAD} == by_hand.SPREAD
    assert m.traffic(cell["traffic"])["kind"] == "backlog"
    e2e = {x["name"] for x in m.metrics_for(CELL, "end_to_end")}
    assert e2e == {"bound_pods_per_s", "setup_s"}


def test_the_config_entry_names_the_file_that_was_found_by_name():
    m = Manifest()
    entry = next(c for c in m.doc["configs"] if c["name"] == CONFIG)
    by_name = os.path.join(m.bench_dir, "configs", CONFIG + ".json")
    assert os.path.samefile(os.path.join(m.root, entry["file"]), by_name)
    doc = m.config(CONFIG)
    assert doc["name"] == CONFIG and doc["source"] == entry["source"]
    assert set(doc["reduced"]) == set(entry["reduced"]) == {"measurePods"}
    # nothing of the cluster is cut, and the guarantee the cell adds is stated
    params = doc["test_case"]["workloads"][0]["params"]
    assert (params["initNodes"], params["initPods"]) == (5000, 5000)
    assert "max_skew" in doc["guarantees"] and "why_zone" in doc["assumed"]


def test_the_roofline_of_the_plain_solve_is_not_read_in_this_cell():
    names = {x["name"] for x in Manifest().metrics_for(CELL, "per_layer")}
    assert "solve_roofline.backlog" not in names      # its bytes leave the tables out
    assert NEW <= names
    control = {x["name"] for x in Manifest().metrics_for("perf5k-basic-closed256", "per_layer")}
    assert {"solve_waves_per_cycle.backlog", "solve_wave_fallbacks_per_cycle.backlog"} <= control
    assert not {"spread_rows_per_cycle.backlog",
                "constraint_encode_ms_per_cycle.backlog"} & control


# under the suite's load a toy run builds its executables slowly: the replay
# may wait for them and the drain for the window's closing wave, so that the
# 3 s window holds whole waves and no build
STEADY = dict(by_hand.FAST, replay_cap_s=20.0, drain_s=30.0)


@pytest.fixture(scope="module")
def toy_record():
    """The module's one toy record, read by every case; what the program's
    recorder holds of it is read here, before any other run in this process."""
    from perfbench import programtrace, reduce

    m = Manifest()
    # a 3 s window that held no whole wave (a build inside it): the next run finds it built
    for _ in range(3):
        rec = harness.run_cell(
            m, m.cell(CELL), 2**31 + 27, 3.0, False, True,
            t_start=time.perf_counter(), overrides=STEADY,
        )
        if reduce.edges(rec) is not None:
            break
    programtrace.load(rec)
    return rec


@pytest.mark.parametrize(
    "metric", [x["name"] for x in Manifest().metrics_for(CELL, "per_layer")]
)
def test_every_listed_per_layer_metric_reads_a_number(toy_record, metric):
    value = Manifest().reader("per_layer", metric)(toy_record)
    if metric in DEVICE_ONLY:
        assert value is None        # never a CPU number under a device metric's name
        return
    assert value is not None and float(value) == float(value), metric
    if metric == "spread_rows_per_cycle.backlog":
        assert 1 <= value <= 16     # a row a namespace
    if metric == "solve_wave_fallbacks_per_cycle.backlog":
        assert value >= 0


def test_the_toy_run_is_correct_and_holds_the_new_spans(toy_record):
    from perfbench import programtrace

    assert toy_record["verdict"]["correct"], toy_record["verdict"]["checks"]
    pt = programtrace.load(toy_record)
    assert pt is not None and pt["dropped_spans"] == 0 and pt["dropped_pods"] == 0
    by_name = {}
    for s in pt["spans"]:
        by_name.setdefault(s["name"], []).append(s)
    for name in ("sched.encode.classes", "sched.solve.waves"):
        assert by_name.get(name), name
        assert all(s["cycle"] > 0 for s in by_name[name]), name     # under a cycle
    # the wave count is a direct child of its cycle: a slow cycle's line names it
    assert all(s["parent"] == s["cycle"] for s in by_name["sched.solve.waves"])
    classes = by_name["sched.encode.classes"]
    assert all(s["a1"] == 32 for s in classes if s["n"] > 0)   # the padded class dim
    assert all(1 <= s["a0"] <= 17 for s in classes)
