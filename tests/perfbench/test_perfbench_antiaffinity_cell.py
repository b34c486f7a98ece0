"""The cell ``perf5k-antiaffinity-closed256-live2000`` as BENCHMARK.json lists
it (PR 32): the entries say what ``test_perfbench_antiaffinity.py`` spelled out
by hand while the configuration had no cell, the file is what that module's
unlisted case pinned, the served program runs the listed cell at toy size and
comes out correct with deletions made, the cell's checks keep their names,
limits and order, and the three per-layer metrics the cell brings read a number
where they should and nothing where they should not (counts on the CPU; never a
device number)."""

import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

from perfbench import harness, roofline, roofline_interpod  # noqa: E402
from perfbench import run as bench  # noqa: E402
from perfbench.manifest import Manifest  # noqa: E402

import test_perfbench_antiaffinity as by_hand  # noqa: E402

DOC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = by_hand.CELL["name"]
CONFIG = by_hand.CONFIG
CONTROL = "perf5k-basic-closed256"
TERMS = "interpod_term_rows_per_cycle.backlog"
REMOVE = "cache_remove_us_per_pod.backlog"
ROOF = "solve_roofline_interpod.backlog"
STEPS = "solve_wave_steps_per_pod.backlog"      # PR 34: the three closed cells
NEW = {TERMS, REMOVE, ROOF}
# what only a chip holds (its profiler trace, its memory statistics): no CPU run reads these
DEVICE_ONLY = {"solve_device_us_per_pod.backlog", "device_idle_share.backlog",
               "peak_device_bytes", ROOF}
# listed for other cells and not for this one: no spread row here, and the plain roofline's
# bytes leave the term tables out
NOT_HERE = {"spread_rows_per_cycle.backlog", "solve_roofline.backlog"}
CHECKS = by_hand.ALWAYS + ["colocated_pods"] + by_hand.DELETES
TALLIED = -1


def per_layer(cell):
    return [x["name"] for x in Manifest().metrics_for(cell, "per_layer")]


# -- the entries -------------------------------------------------------------------

def test_the_cell_is_what_was_spelled_out_by_hand():
    m = Manifest()
    cell = m.cell(CELL)
    assert {k: cell[k] for k in by_hand.CELL} == by_hand.CELL
    assert DOC["workloads"][-1] == cell and len(DOC["workloads"]) == 4     # added at the end
    mix = m.traffic(cell["traffic"])
    assert (mix["kind"], mix["backlog_pods"], mix["topup_chunk"], mix["creators"]) == (
        "backlog", 256, 32, 4)
    assert mix["live_pods"] == 2000 and mix["trace_seconds"] == 5.0
    assert {x["name"] for x in m.metrics_for(CELL, "end_to_end")} == {
        "bound_pods_per_s", "setup_s"}
    for word in ("term tables", "one wave a pod", "remove_pod", "deleted"):
        assert word in cell["why"], word


def test_the_config_entry_names_the_file_the_unlisted_case_pinned():
    m = Manifest()
    entry = DOC["configs"][-1]
    assert entry["name"] == CONFIG and len(DOC["configs"]) == 3
    by_name = os.path.join(m.bench_dir, "configs", CONFIG + ".json")
    assert os.path.samefile(os.path.join(m.root, entry["file"]), by_name)
    doc = m.config(CONFIG)
    assert doc["name"] == CONFIG and doc["source"] == entry["source"]
    assert "SchedulingPodAntiAffinity/5000Nodes" in doc["source"]
    assert set(doc["reduced"]) == set(entry["reduced"]) == {"measurePods"}
    for word in ("term tables", "5,000-domain", "term owner", "complete"):
        assert word in entry["why"], word
    # what test_the_files_are_in_the_tree_and_no_entry_names_them_yet held of the file, and
    # still holds now that an entry names it
    assert doc["capacity_pods"] == 5000 and "200,000" in doc["capacity_note"]
    assert set(doc["guarantees"]) == {"bound_exactly_once", "fits", "durable", "rv_monotone",
                                      "anti_affinity"}
    assert doc["assumed"]["scheduler"] == {"batch_size": 1024} and doc["assumed"]["pods_complete"]
    assert doc["assumed"]["store"] == m.config("sched-perf-5000n")["assumed"]["store"]
    # nothing of the cluster is cut
    params = doc["test_case"]["workloads"][0]["params"]
    assert (params["initNodes"], params["initPods"], params["measurePods"]) == (5000, 1000, 1000)


def test_the_metrics_the_cell_lists_and_those_it_does_not():
    names = set(per_layer(CELL))
    assert NEW <= names and not NOT_HERE & names
    shared = set(per_layer("perf5k-spread-closed256")) - NOT_HERE
    assert shared <= names                    # every reader the closed cells share
    assert names - shared == NEW
    control = set(per_layer(CONTROL))
    assert NEW & control == {TERMS}           # the control reads 0 term rows, removes nothing
    by_name = {x["name"]: x for x in DOC["per_layer"]}
    assert [x["name"] for x in DOC["per_layer"][-4:]] == [TERMS, REMOVE, ROOF, STEPS]
    assert by_name[STEPS]["workloads"] == [CONTROL, "perf5k-spread-closed256", CELL]
    assert (by_name[STEPS]["unit"], by_name[STEPS]["better"], by_name[STEPS]["source"]) == (
        "steps/pod", "lower", "program_counter")
    assert by_name[STEPS]["layer"] == by_name["solve_waves_per_cycle.backlog"]["layer"]
    assert by_name[STEPS]["moves"] == "bound_pods_per_s"
    assert by_name["events_cpu_share.backlog"]["workloads"][-1] == CELL     # appended, PR 34
    assert by_name[TERMS]["workloads"] == [CELL, CONTROL]
    assert by_name[REMOVE]["workloads"] == by_name[ROOF]["workloads"] == [CELL]
    assert (by_name[TERMS]["unit"], by_name[TERMS]["source"]) == ("rows", "program_counter")
    assert (by_name[REMOVE]["unit"], by_name[REMOVE]["better"], by_name[REMOVE]["source"]) == (
        "us/pod", "lower", "program_span")
    assert (by_name[ROOF]["unit"], by_name[ROOF]["source"], by_name[ROOF]["layer"]) == (
        "%", "device_trace", "kernels")
    assert all(by_name[n]["moves"] == "bound_pods_per_s" for n in NEW)
    assert by_name[TERMS]["layer"] == by_name["encode_us_per_pod.backlog"]["layer"]
    # a name appended to a list, and nothing else of an accepted entry changed
    assert by_name["cycle_pods_p50.backlog"]["workloads"] == [
        CONTROL, "perf5k-spread-closed256", CELL]
    assert next(e for e in DOC["end_to_end"] if e["name"] == "bound_pods_per_s")["workloads"] == [
        CONTROL, "perf5k-spread-closed256", CELL]


# -- the served program runs the listed cell at toy size ---------------------------

# under the suite's load a toy run builds its executables slowly: the replay
# may wait for them and the drain for the window's closing wave, so that the
# 3 s window holds whole waves and no build
STEADY = dict(by_hand.FAST, replay_cap_s=20.0, drain_s=30.0)


def toy(cell, seed):
    """One toy record of a listed cell; what the program's recorder holds of
    it is read here, before any other run in this process."""
    from perfbench import programtrace, reduce

    m = Manifest()
    # a 3 s window that held no whole wave (a build inside it): the next run finds it built
    for _ in range(3):
        rec = harness.run_cell(
            m, m.cell(cell), seed, 3.0, False, True,
            t_start=time.perf_counter(), overrides=STEADY,
        )
        if reduce.edges(rec) is not None:
            break
    programtrace.load(rec)
    return rec


@pytest.fixture(scope="module")
def toy_record():
    return toy(CELL, 2**31 + 32)


@pytest.fixture(scope="module")
def control_record():
    return toy(CONTROL, 2**31 + 33)


def test_the_toy_run_is_correct_and_its_checks_keep_names_limits_and_order(toy_record):
    v = toy_record["verdict"]
    assert v["correct"], v["checks"]
    # the ALWAYS keys, then the rule's, then the two of a mix that deletes: a rule file
    # added later whose `applies` is too wide shows here (rules/__init__.py scans the directory)
    assert list(v["checks"]) == CHECKS
    assert all(pair == [0, 0] for pair in v["checks"].values()), v["checks"]
    assert len(toy_record["deleted"]) > 0 and len(toy_record["gone"]) > 0
    assert {ns for ns, _, _, _, _ in toy_record["created"]} == {"sched-1"}


@pytest.mark.parametrize("metric", per_layer(CELL))
def test_every_listed_per_layer_metric_reads_a_number(toy_record, metric):
    value = Manifest().reader("per_layer", metric)(toy_record)
    if metric in DEVICE_ONLY:
        assert value is None        # never a CPU number under a device metric's name
        return
    assert value is not None and float(value) == float(value), metric
    if metric == TERMS:
        assert value == 1.0         # every pod carries the one term
    if metric == REMOVE:
        assert value > 0.0
    if metric == STEPS:
        assert value >= 1.0         # one step a pod is the floor; pads read more
    if metric == "events_cpu_share.backlog":
        assert 0.0 <= value <= 100.0
    if metric == "encode_bound_entries_read_per_cycle.backlog":
        assert value >= 24          # the init pods at least: every bound pod is an owner


def test_the_toy_run_holds_the_new_row_and_tally(toy_record):
    from perfbench import programtrace

    pt = programtrace.load(toy_record)
    assert pt is not None and pt["dropped_spans"] == 0 and pt["dropped_pods"] == 0
    rows = programtrace.spans_named(toy_record, ("sched.encode.terms",))
    encodes = programtrace.spans_named(toy_record, ("sched.encode.classes",))
    assert rows and len(rows) == len(encodes)                   # one an encode
    assert all(s["cycle"] > 0 and s["start"] == s["end"] for s in rows)
    assert all(s["n"] == 1 for s in rows)
    n_nodes = Manifest().config(CONFIG)["toy"]["initNodes"]
    assert all(s["a1"] == n_nodes for s in rows)                # hostnames: the key's domains
    assert all(24 <= s["a0"] <= n_nodes for s in rows)          # owners read: the bound pods
    tallies = programtrace.spans_named(toy_record, ("sched.cache.remove",))
    assert tallies and all(s["parent"] == TALLIED and s["cycle"] == 0 for s in tallies)
    assert len({s["thread"] for s in tallies}) == 1             # the Pod informer's thread
    e = pt["edges"]
    asked = sum(1 for d in toy_record["deleted"] if e[0] <= d[2] < e[1])
    removed = sum(s["n"] for s in tallies)
    # a row sums a tenth of a second and the informer runs behind the acknowledgement, so
    # the edges cut the two counts a few chunks apart
    slack = 3 * toy_record["params"]["topup_chunk"] + asked // 10
    assert asked > 0 and abs(removed - asked) <= slack, (removed, asked)


def test_the_control_reads_no_term_row_and_removes_nothing(control_record):
    m = Manifest()
    assert control_record["verdict"]["correct"]
    assert m.reader("per_layer", TERMS)(control_record) == 0.0
    assert m.reader("per_layer", REMOVE)(control_record) is None
    line = bench.result_line(m, m.cell(CONTROL), control_record, True)
    assert line["metrics"][TERMS] == {"value": 0.0, "unit": "rows"}
    assert REMOVE not in line["metrics"] and ROOF not in line["metrics"]


def test_the_line_leaves_the_new_metrics_out_on_a_program_without_them(toy_record):
    from perfbench import programtrace

    m = Manifest()
    line = bench.result_line(m, m.cell(CELL), toy_record, True)
    assert line["correct"] is True and line["failed"] == 0
    assert {TERMS, REMOVE} <= set(line["metrics"]) and ROOF not in line["metrics"]
    assert list(line["checks"]) == CHECKS
    rec = dict(toy_record)
    pt = dict(programtrace.load(rec))
    pt["spans"] = [s for s in pt["spans"]                          # the parent's recorder
                   if s["name"] not in ("sched.encode.terms", "sched.cache.remove")]
    rec["_programtrace"] = pt
    line = bench.result_line(m, m.cell(CELL), rec, True)
    assert not NEW & set(line["metrics"]) and len(line["metrics"]) >= 20


# -- the three readers on records whose rows are given -----------------------------

def row(name, start, n, a0=0.0, a1=0.0, end=None, parent=7):
    return {"name": name, "start": start, "end": start if end is None else end, "n": n,
            "a0": a0, "a1": a1, "parent": parent, "cycle": 7}


def test_term_rows_is_the_mean_of_the_rows_between_the_edges():
    read = Manifest().reader("per_layer", TERMS)
    spans = [row("sched.encode.terms", 101.0, 1, 2150, 5000),
             row("sched.encode.terms", 102.0, 3, 2150, 5000),
             row("sched.encode.classes", 102.0, 16)]
    rec = {"_programtrace": {"edges": (100.0, 110.0), "spans": spans}}
    assert read(rec) == 2.0
    rec["_programtrace"]["spans"] = spans[2:]       # a recorder without the row: the parent
    assert read(rec) is None
    assert read({"_programtrace": None}) is None    # no recorder: the plain reference


def test_cache_remove_is_the_tallies_seconds_over_their_pods():
    read = Manifest().reader("per_layer", REMOVE)
    spans = [row("sched.cache.remove", 101.0, 32, a0=0.016, end=101.09, parent=TALLIED),
             row("sched.cache.remove", 101.2, 96, a0=0.048, end=101.29, parent=TALLIED),
             row("store.create", 101.0, 40, a0=0.1, end=101.1, parent=TALLIED)]
    rec = {"_programtrace": {"edges": (100.0, 110.0), "spans": spans}}
    assert read(rec) == pytest.approx(500.0)        # 0.064 s over 128 pods
    rec["_programtrace"]["spans"] = spans[2:]       # nothing removed, or no such tally
    assert read(rec) is None
    assert read({"_programtrace": None}) is None


def test_wave_steps_per_pod_is_the_rows_steps_over_their_cycles_pods():
    read = Manifest().reader("per_layer", STEPS)

    def cycle(i, start, pods):
        return {"id": i, "name": "sched.cycle", "start": start, "end": start + 0.1, "n": pods,
                "a0": 0.0, "a1": 0.0, "parent": 0, "cycle": i}

    def waves(cyc, start, n, steps):
        return dict(row("sched.solve.waves", start, n, a1=steps), cycle=cyc, parent=cyc)

    spans = [cycle(7, 101.0, 85), waves(7, 101.05, 86, 128.0),      # 84 single steps, 32 and 12
             cycle(8, 102.0, 128), waves(8, 102.05, 128, 128.0),    # a full bucket: the floor
             cycle(9, 103.0, 10),                                   # a greedy cycle: no row
             waves(5, 100.5, 4, 64.0)]      # its cycle began before the first edge: left out
    rec = {"_programtrace": {"edges": (100.0, 110.0), "spans": spans}}
    assert read(rec) == pytest.approx(256.0 / 213.0)
    rec["_programtrace"]["spans"] = spans[2:4]
    assert read(rec) == 1.0
    # the parent's rows carry no step count: nothing, never 0 under a floor of 1
    rec["_programtrace"]["spans"] = [cycle(8, 102.0, 128), waves(8, 102.05, 128, 0.0)]
    assert read(rec) is None
    rec["_programtrace"]["spans"] = [cycle(9, 103.0, 10)]           # no solve took the route
    assert read(rec) is None
    assert read({"_programtrace": None}) is None                    # no recorder


@pytest.mark.parametrize("shape,expect", [
    ((128, 8192, 4, 1), 4 * (8192 * 4 + 128 * 4) + 8 * 128 * 8192 + 8 * 1 * 8192),
    ((256, 8192, 4, 3), 4 * (8192 * 4 + 256 * 4) + 8 * 256 * 8192 + 8 * 3 * 8192),
    ((64, 8192, 6, 0), roofline.solve_min_bytes(64, 8192, 6)),     # no term: the plain solve
])
def test_interpod_roofline_bytes_are_a_function_of_the_shapes_alone(shape, expect):
    assert roofline_interpod.solve_min_bytes(*shape) == expect
    assert roofline_interpod.solve_min_seconds(*shape, 819e9) == pytest.approx(expect / 819e9)


def test_interpod_roofline_share_follows_the_plain_shares_arithmetic():
    read = Manifest().reader("per_layer", ROOF)
    cycles = [
        {"P": 128, "N": 8192, "R": 4, "t_dispatch0": 101.0, "t_dispatch1": 101.1,
         "t_decode1": 101.4},
        {"P": 256, "N": 8192, "R": 4, "t_dispatch0": 102.0, "t_dispatch1": 102.1,
         "t_decode1": 102.7},
        {"P": 128, "N": 8192, "R": 4, "t_dispatch0": 104.9, "t_dispatch1": 105.0,
         "t_decode1": 105.3},                       # ends after the slice: left out
        {"t_decode1": 103.0},                       # a cycle with no dispatch
    ]
    spans = [row("sched.encode.terms", 101.05, 1), row("sched.encode.terms", 102.05, 1),
             row("sched.encode.terms", 104.95, 1)]
    rec = {
        "device": {"kind": "TPU v5 lite"}, "cycles": cycles, "trace_window": (100.0, 105.0),
        # three executions in the slice took 0.9 s on the device: 0.3 s each
        "trace": {"modules": {"jit_run_warm": [0.9, 3], "jit__unpack": [0.01, 3]}},
        "_programtrace": {"edges": (90.0, 110.0), "spans": spans},
    }
    least = (roofline_interpod.solve_min_seconds(128, 8192, 4, 1, 819e9)
             + roofline_interpod.solve_min_seconds(256, 8192, 4, 1, 819e9))
    assert read(rec) == pytest.approx(100.0 * least / (0.3 * 2))
    assert 0.0 < read(rec) < 0.01                   # one wave a pod: thousandths of a percent
    assert read(dict(rec, trace=None)) is None      # no device trace: a CPU run
    no_rows = dict(rec, _programtrace={"edges": (90.0, 110.0), "spans": []})
    assert read(no_rows) is None                    # the parent: no such row
    assert read(dict(rec, _programtrace=None)) is None
