"""The cell ``perf5k-unschedulable-closed256-live5000`` as BENCHMARK.json lists
it (PR 35): the entries say what ``test_perfbench_unplaceable.py`` spelled out by
hand while the configuration had no cell; the standing lists gained the new name
at their end and nothing else of an accepted entry changed; every role of every
*other* listed configuration is placeable; the configuration's ops and parameters
are what its ``source_note`` recalls of upstream; each reader this cell brings
does its arithmetic on a record whose rows are given and reads nothing on a
program without them; and the served program runs the listed cell at toy size,
correct, its pending pods pending, nothing built after ``warmup`` (counts on the
CPU; never a device number).

Nine cases of older modules state the tree before this cell was listed and are
deselected in ``pytest.ini``; what they held that is still true is restated here."""

import hashlib
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

from perfbench import harness, reference  # noqa: E402
from perfbench import run as bench  # noqa: E402
from perfbench.deployment import Deployment  # noqa: E402
from perfbench.manifest import Manifest  # noqa: E402

import test_perfbench_unplaceable as by_hand  # noqa: E402

DOC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = by_hand.CELL["name"]
CONFIG = by_hand.CONFIG
BASIC, STEADY_CELL = "perf5k-basic-closed256", "perf5k-basic-steady"
SPREAD, ANTI = "perf5k-spread-closed256", "perf5k-antiaffinity-closed256-live2000"
CLOSED = [BASIC, SPREAD, ANTI]
FAILED, AUCTION = "solve_failed_slots_share.backlog", "solve_auction_slots_share.backlog"
WAKE_RATE, WAKE_COST = "queue_wake_pods_per_s.backlog", "queue_wake_us_per_pod.backlog"
FAIL_COST, PARKED = "fail_branch_us_per_pod.backlog", "queue_parked_pods_p50.backlog"
BUILDS, WALK_COST = "builds_after_warmup", "queue_wake_walk_us_per_call.backlog"
ROOF = "solve_roofline.backlog"
NEW = [FAILED, AUCTION, WAKE_RATE, WAKE_COST, FAIL_COST, PARKED, BUILDS, WALK_COST]
FROM_THE_RECORDER = {WAKE_RATE, WAKE_COST, FAIL_COST, PARKED, WALK_COST}
# the accepted manifest of PR 34 (json.dumps(doc, sort_keys=True), sha256)
PARENT = "2f7ef458cb1548292b110f15ba0d3be7e25573db67810f205aafdef71171b18b"
# what only a chip holds: no CPU run reads these
DEVICE_ONLY = {"solve_device_us_per_pod.backlog", "device_idle_share.backlog", "peak_device_bytes",
               ROOF}
CHECKS = by_hand.CHECKS         # ALWAYS + ["bound_unplaceable"] + the deletions' two
BY_NAME = {x["name"]: x for x in DOC["per_layer"]}


def per_layer(cell):
    return [x["name"] for x in Manifest().metrics_for(cell, "per_layer")]


# -- the entries -------------------------------------------------------------------

def test_the_cell_is_the_last_of_five_and_what_was_spelled_out_by_hand():
    m = Manifest()
    cell = m.cell(CELL)
    assert {k: cell[k] for k in by_hand.CELL} == by_hand.CELL and cell["chips"] == 1
    assert DOC["workloads"][-1] == cell and len(DOC["workloads"]) == 5
    mix = m.traffic(cell["traffic"])
    assert (mix["kind"], mix["backlog_pods"], mix["topup_chunk"], mix["creators"]) == (
        "backlog", 256, 32, 4)
    assert (mix["live_pods"], mix["warmup_pods"], mix["replay_pods"], mix["trace_seconds"]) == (
        5000, 1024, 2048, 10.0)
    assert "rate_pods_per_s" not in mix                 # a closed loop: no rate
    assert {x["name"] for x in m.metrics_for(CELL, "end_to_end")} == {
        "bound_pods_per_s", "setup_s"}
    for word in ("256 / loop latency", "NOT saturation", "completions", "pending pods",
                 "single pods", "constraints"):
        assert word in cell["why"], word
    assert len(cell["why"]) <= 200


def test_the_config_entry_is_the_last_of_four_and_names_the_file():
    m = Manifest()
    entry = DOC["configs"][-1]
    assert entry["name"] == CONFIG and len(DOC["configs"]) == 4
    by_name = os.path.join(m.bench_dir, "configs", CONFIG + ".json")
    assert os.path.samefile(os.path.join(m.root, entry["file"]), by_name)
    doc = m.config(CONFIG)
    assert doc["name"] == CONFIG and doc["source"] == entry["source"]
    assert entry["source"].endswith("performance-config.yaml#Unschedulable/5000Nodes/2000InitPods")
    assert len(entry["source"]) == 153
    assert set(doc["reduced"]) == set(entry["reduced"]) == {"measurePods"}
    for word in ("pending tiers", "failure branch", "auction route", "two request shapes",
                 "complete"):
        assert word in entry["why"], word
    # what test_the_files_are_in_the_tree_and_no_entry_names_them_yet held of the files,
    # and still holds now that entries name them
    assert CONFIG in {c["name"] for c in DOC["configs"]}
    assert by_hand.MIX in {w["traffic"] for w in DOC["workloads"]}
    assert doc["capacity_pods"] == 200000
    assert set(doc["guarantees"]) == {"bound_exactly_once", "fits", "durable", "rv_monotone",
                                      "unplaceable_stay_pending"}
    basic = m.config("sched-perf-5000n")
    for key in ("store", "scheduler", "namespaces"):
        assert doc["assumed"][key] == basic["assumed"][key]
    assert "AssignedPodDelete" in doc["assumed"]["pods_complete"]


def test_the_configuration_is_what_its_source_note_recalls_of_upstream():
    """Pinned here and not against the port's performance-config.yaml, whose case
    of the same name is another shape (5 unschedulable nodes, 500 pods)."""
    doc = Manifest().config(CONFIG)
    case = doc["test_case"]
    assert case["name"] == "Unschedulable"
    assert case["workloadTemplate"] == [
        {"opcode": "createNodes", "countParam": "$initNodes"},
        {"opcode": "createPods", "countParam": "$initPods",
         "podTemplatePath": "config/pod-large-cpu.yaml", "skipWaitToCompletion": True},
        {"opcode": "createPods", "countParam": "$measurePods",
         "podTemplatePath": "config/pod-default.yaml", "collectMetrics": True},
    ]
    assert case["workloads"] == [{"name": "5000Nodes/2000InitPods", "params": {
        "initNodes": 5000, "initPods": 2000, "measurePods": 5000}}]
    assert doc["workload"] == "5000Nodes/2000InitPods"
    note = doc["source_note"]
    for word in ("not fetched", "skipWaitToCompletion: true", "collectMetrics: true",
                 "5000Nodes/2000InitPods (5000/2000/5000)", "another shape"):
        assert word in note, word


def test_nothing_that_was_there_changed_but_lists_that_gained_the_new_name_at_their_end():
    doc = json.loads(json.dumps(DOC))
    assert [c["name"] for c in doc["configs"][3:]] == [CONFIG]
    assert [w["name"] for w in doc["workloads"][4:]] == [CELL]
    assert [x["name"] for x in doc["per_layer"][59:]] == NEW
    doc["configs"], doc["workloads"] = doc["configs"][:3], doc["workloads"][:4]
    doc["per_layer"] = doc["per_layer"][:59]
    gained = []
    for x in doc["end_to_end"] + doc["per_layer"]:
        assert CELL not in x.get("workloads", [])[:-1]          # never first or in the middle
        if x.get("workloads", [None])[-1] == CELL:
            x["workloads"].pop()
            gained.append(x["name"])
    assert hashlib.sha256(json.dumps(doc, sort_keys=True).encode()).hexdigest() == PARENT
    # the end-to-end metric, every reader the three closed cells share, the two of every
    # cell, the removal tally (pods complete here) and the plain solve's roofline (the
    # cell runs the solve layer, and the auction kernel in no other cell: perfbench/roofline.py
    # reads P, N and R alone); no constraint table, so no spread rows, no constraint span, no
    # term rows and not the term tables' roofline
    shared = [x["name"] for x in doc["per_layer"] if set(CLOSED) <= set(x.get("workloads", []))]
    assert gained == ["bound_pods_per_s"] + [
        x["name"] for x in doc["per_layer"]
        if x["name"] in shared or x["name"] in ("cache_remove_us_per_pod.backlog", ROOF)]
    assert {"setup_compile_s", "peak_device_bytes", "events_cpu_share.backlog",
            "solve_wave_steps_per_pod.backlog"} <= set(gained) and len(gained) == 34
    assert BY_NAME[ROOF]["workloads"] == [BASIC, CELL]
    assert BY_NAME["events_cpu_share.backlog"]["workloads"] == CLOSED + [CELL]
    assert BY_NAME["cache_remove_us_per_pod.backlog"]["workloads"] == [ANTI, CELL]
    assert BY_NAME["setup_compile_s"]["workloads"] == [BASIC, STEADY_CELL, SPREAD, ANTI, CELL]
    assert not {"spread_rows_per_cycle.backlog", "constraint_encode_ms_per_cycle.backlog",
                "interpod_term_rows_per_cycle.backlog",
                "solve_roofline_interpod.backlog"} & set(gained)
    assert next(e for e in DOC["end_to_end"] if e["name"] == "bound_pods_per_s")["workloads"] == (
        CLOSED + [CELL])


def test_the_new_metrics_are_listed_where_their_readers_find_something_to_read():
    solve = BY_NAME["solve_waves_per_cycle.backlog"]["layer"]
    queue = BY_NAME["cycle_pods_p50.backlog"]["layer"]
    want = {
        FAILED: ("%", "program_counter", solve, [CELL, BASIC]),
        AUCTION: ("%", "program_counter", solve, [CELL, BASIC]),
        WAKE_RATE: ("pods/s", "program_counter", queue, [CELL, ANTI]),
        # seconds over pods woken: where nothing is woken there is nothing to divide by, so
        # the anti-affinity cell, which wakes nothing, is on the rate's list alone
        WAKE_COST: ("us/pod", "program_span", queue, [CELL]),
        WALK_COST: ("us/call", "program_span", queue, [CELL]),
        FAIL_COST: ("us/pod", "program_span", BY_NAME["stage_solve_p50_s.backlog"]["layer"], [CELL]),
        PARKED: ("pods", "program_counter", queue, [CELL]),
        BUILDS: ("count", "program_counter", BY_NAME["setup_compile_s"]["layer"],
                 [BASIC, STEADY_CELL, SPREAD, ANTI, CELL]),
    }
    for name, (unit, source, layer, cells) in want.items():
        x = BY_NAME[name]
        assert set(x) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert (x["unit"], x["source"], x["layer"], x["workloads"]) == (unit, source, layer, cells)
        assert x["better"] == "lower"
        assert x["moves"] == ("setup_s" if name == BUILDS else "bound_pods_per_s")
        assert os.path.isfile(os.path.join(ROOT, "perfbench", "metrics", name + ".py"))
    names = set(per_layer(CELL))
    assert set(NEW) <= names
    assert names - set(NEW) - {"cache_remove_us_per_pod.backlog", ROOF} == (
        set(per_layer(SPREAD)) - {"spread_rows_per_cycle.backlog", BUILDS,
                                  "constraint_encode_ms_per_cycle.backlog"})
    # no new kernel, so no new roofline: every `kernels` metric is one the parent had
    assert [x["name"] for x in DOC["per_layer"] if x["layer"] == "kernels"] == [
        "solve_roofline.backlog", "solve_roofline_interpod.backlog"]


# -- a role is placeable or it is not, by the rules alone ---------------------------

@pytest.mark.parametrize("toy", [True, False])
@pytest.mark.parametrize("config", [c["name"] for c in DOC["configs"] if c["name"] != CONFIG])
def test_every_role_of_every_other_listed_configuration_is_placeable(config, toy):
    dep = Deployment(Manifest().config(config), toy=toy)
    assert reference.unplaceable_roles(dep) == frozenset()
    assert dep.skip_wait == {"init": False, "measure": False}
    ledger = reference.Ledger(dep.nodes(), dep.templates)
    assert all(ledger.placeable(role, dep.namespace_of(role)) for role in dep.templates)


def test_the_listed_configuration_has_the_one_role_that_is_not():
    listed = {c["name"]: reference.unplaceable_roles(Deployment(Manifest().config(c["name"]),
                                                                toy=True))
              for c in DOC["configs"]}
    assert {k for k, v in listed.items() if v} == {CONFIG}
    assert listed[CONFIG] == frozenset({"init"})


# -- the readers on records whose rows are given ------------------------------------

def row(name, start, n=0, a0=0.0, a1=0.0, end=None):
    return {"name": name, "start": start, "end": start if end is None else end, "n": n,
            "a0": a0, "a1": a1, "parent": 0, "cycle": 0}


def traced(spans):
    return {"_programtrace": {"edges": (100.0, 110.0), "spans": spans}}


def solves(cycles):
    # the window's own edges (an open loop's): the solves dispatched between them
    return {"kind": "open_loop", "t_open": 100.0, "t_close": 110.0, "cycles": cycles}


def cyc(t, pods, placed, route):
    return {"t_dispatch0": t, "pods": pods, "placed": placed, "route": route}


DEPTH = [row("sched.queue.depth", 101.0, 0, 1976.0, 24.0), row("sched.queue.depth", 102.0, 3, 0.0, 0.0),
         row("sched.queue.depth", 103.0, 0, 0.0, 2000.0)]
RETURN = [cyc(101.0, 1024, 48, "auction"), cyc(101.3, 1024, 0, "auction"),
          cyc(101.6, 300, 252, "wavefront"), cyc(102.0, 40, 40, "greedy"),
          cyc(99.0, 1024, 0, "auction"), cyc(110.0, 1024, 0, "auction")]   # outside the edges

CASES = [
    # (reader, record, what it reads)
    (FAILED, solves(RETURN), pytest.approx(100.0 * 2048 / 2388)),
    (FAILED, solves([cyc(101.0, 128, 128, "wavefront")]), 0.0),          # the control
    (FAILED, solves(RETURN[4:]), None),                                  # an empty window
    (AUCTION, solves(RETURN), pytest.approx(100.0 * 2048 / 2388)),
    (AUCTION, solves([cyc(101.0, 128, 128, "wavefront")]), 0.0),
    (AUCTION, solves([]), None),
    (WAKE_RATE, traced(DEPTH + [row("sched.queue.wake", 101.0, 2000, 1976.0, 24.0, end=101.02),
                                row("sched.queue.wake", 104.0, 0, end=104.001)]), 200.0),
    (WAKE_RATE, traced(DEPTH), 0.0),            # events came and nothing was parked
    (WAKE_RATE, traced([row("sched.encode", 101.0)]), None),             # the parent's recorder
    (WAKE_RATE, {"_programtrace": None}, None),                          # no recorder at all
    (WAKE_COST, traced([row("sched.queue.wake", 101.0, 2000, 1976.0, 24.0, end=101.02),
                        row("sched.queue.wake", 104.0, 0, end=104.001)]),
     pytest.approx(10.0)),                      # 0.020 s over 2,000 pods: not the idle walk's
    (WAKE_COST, traced(DEPTH + [row("sched.queue.wake", 104.0, 0, end=104.001)]), None),
    (WAKE_COST, {"_programtrace": None}, None),
    (WALK_COST, traced([row("sched.queue.wake", 101.0, 2000, 1976.0, 24.0, end=101.02),
                        row("sched.queue.wake", 104.0, 0, end=104.001)]),
     pytest.approx(10500.0)),                   # 0.021 s over two calls, woken or not
    (WALK_COST, traced(DEPTH), None),           # no call found a pod parked
    (WALK_COST, {"_programtrace": None}, None),
    (FAIL_COST, traced([row("sched.fail", 101.0, 976, 0.0488, 0.0, end=101.06),
                        row("sched.fail", 101.3, 1024, 0.0512, 24.0, end=101.36)]),
     pytest.approx(50.0)),                      # 0.1 s over 2,000 pods
    (FAIL_COST, traced(DEPTH), None),           # every pod bound
    (FAIL_COST, {"_programtrace": None}, None),
    (PARKED, traced(DEPTH), 2000.0),
    (PARKED, traced([row("sched.pop_wait", 101.0, 8)]), None),
    (PARKED, {"_programtrace": None}, None),
    (BUILDS, {"t_warmup_end": 50.0, "t_open": 100.0, "compiles": [
        (40.0, "backend_compile", "jit(run_warm)", 1.0),        # in warmup
        (60.0, "backend_compile", "jit(run_warm)", 0.9), (60.0, "cache_load", "?", 0.8),
        (61.0, "trace", "run_warm", 0.1), (99.0, "backend_compile", "jit(_unpack)", 0.1),
        (100.5, "backend_compile", "jit(run)", 2.0)]}, 2.0),    # the last: in the window
    (BUILDS, {"t_warmup_end": 50.0, "t_open": 100.0, "compiles": []}, 0.0),
]


@pytest.mark.parametrize("case", range(len(CASES)), ids=[f"{c[0]}-{i}" for i, c in enumerate(CASES)])
def test_a_new_reader_does_its_arithmetic_on_given_rows_and_reads_nothing_without_them(case):
    name, rec, want = CASES[case]
    got = Manifest().reader("per_layer", name)(dict(rec))
    assert got == want if want is not None else got is None


# -- the served program runs the listed cell at toy size ---------------------------

# the toy mix warms 32 pods a template and its first cycle holds the 200 pending pods:
# `warmup` gets enough of each to reach that bucket, and under the suite's load the
# replay may wait for the walk's builds (test_perfbench_antiaffinity_cell.STEADY)
WARMED = dict(by_hand.STEADY, warmup_pods=128)


@pytest.fixture(scope="module")
def toy_record():
    from perfbench import programtrace, reduce

    m = Manifest()
    # a 3 s window that held no whole wave: the next run finds the machine quieter
    for attempt in range(3):
        rec = harness.run_cell(m, m.cell(CELL), 2**31 + 35 + attempt, 3.0, False, True,
                               t_start=time.perf_counter(), overrides=WARMED)
        if reduce.edges(rec) is not None:
            break
    programtrace.load(rec)      # the recorder is read here, before any other run
    return rec


def test_the_toy_run_is_correct_its_checks_keep_names_limits_and_order_and_the_pending_stay(
        toy_record):
    v = toy_record["verdict"]
    assert v["correct"], v["checks"]
    assert list(v["checks"]) == CHECKS
    assert all(pair == [0, 0] for pair in v["checks"].values()), v["checks"]
    n_init = Manifest().config(CONFIG)["toy"]["initPods"]
    assert toy_record["pending_at_end"] == n_init
    assert not any(name.startswith("init-") for _, name in toy_record["bound"])
    assert len(toy_record["deleted"]) > 0 and len(toy_record["gone"]) > 0


def test_nothing_is_built_between_warmup_and_the_window_with_both_templates_warmed(toy_record):
    read = Manifest().reader("per_layer", BUILDS)
    assert read(toy_record) == 0.0, bench.details(toy_record)["compiles_by_phase"]
    assert Manifest().reader("per_layer", "compiles_in_window.backlog")(toy_record) == 0.0


@pytest.mark.parametrize("metric", per_layer(CELL))
def test_every_listed_per_layer_metric_reads_a_number(toy_record, metric):
    value = Manifest().reader("per_layer", metric)(toy_record)
    if metric in DEVICE_ONLY:
        assert value is None        # never a CPU number under a device metric's name
        return
    if metric in (WAKE_COST, WALK_COST):
        # seconds over pods woken, or over calls that found pods parked, between the
        # edges; where the window's failures all missed an event (below) nothing is
        # parked there and nothing is read
        assert value is None or value > 0.0
        return
    assert value is not None and float(value) == float(value), metric
    if metric == FAILED:
        assert 0.0 < value < 100.0  # the pending pods came back and failed again
    if metric == AUCTION:
        assert value == 0.0         # no toy batch reaches the auction's 1,024 pods
    if metric == PARKED:
        assert value == Manifest().config(CONFIG)["toy"]["initPods"]
    if metric in (WAKE_RATE, FAIL_COST):
        assert value >= 0.0


def test_the_toy_run_holds_the_rows_the_deployment_adds(toy_record):
    from perfbench import programtrace

    pt = programtrace.load(toy_record)
    assert pt is not None and pt["dropped_spans"] == 0
    n_init = Manifest().config(CONFIG)["toy"]["initPods"]
    fails = programtrace.spans_named(toy_record, ("sched.fail",))
    assert fails and all(s["cycle"] > 0 and 0 < s["n"] <= n_init for s in fails)
    assert all(0 <= s["a1"] <= s["n"] and s["a0"] > 0.0 for s in fails)
    # one row a cycle that failed pods, holding what the harness's record of the solves holds
    e = pt["edges"]
    lost = {c["pods"] - c["placed"] for c in toy_record["cycles"]
            if e[0] <= c.get("t_dispatch0", -1.0) < e[1] and c["pods"] > c["placed"]}
    assert {s["n"] for s in fails} <= lost | {n_init} and lost
    depth = programtrace.spans_named(toy_record, ("sched.queue.depth",))
    pops = programtrace.spans_named(toy_record, ("sched.pop_wait",))
    # one a pop that took pods (a pop that began before the first edge writes its row after it)
    assert depth and abs(len(depth) - len([s for s in pops if s["n"] > 0])) <= 1
    assert all(s["start"] == s["end"] and s["a0"] + s["a1"] <= n_init for s in depth)
    # over the whole run the pending pods were woken from `unschedulable` at least once:
    # set-up parks them (nothing completes yet), the first completion wakes them
    from kubernetes_tpu.utils import trace

    snap = trace.snapshot(toy_record["t_start"])
    wakes = [dict(zip(trace.SPAN_FIELDS, r)) for r in snap["spans"]
             if r[1] == "sched.queue.wake"]
    assert sum(s["n"] for s in wakes) >= n_init
    assert all(s["a0"] + s["a1"] == s["n"] and s["cycle"] == 0 for s in wakes)


def test_the_line_leaves_the_recorders_metrics_out_on_a_program_without_the_rows(toy_record):
    from perfbench import programtrace

    m = Manifest()
    line = bench.result_line(m, m.cell(CELL), toy_record, True)
    assert line["correct"] is True and line["failed"] == 0 and list(line["checks"]) == CHECKS
    assert {FAILED, AUCTION, WAKE_RATE, FAIL_COST, PARKED, BUILDS} <= set(line["metrics"])
    assert line["metrics"][BUILDS] == {"value": 0.0, "unit": "count"}
    rec = dict(toy_record)
    pt = dict(programtrace.load(rec))
    pt["spans"] = [s for s in pt["spans"]                          # the parent's recorder
                   if s["name"] not in ("sched.fail", "sched.queue.wake", "sched.queue.depth")]
    rec["_programtrace"] = pt
    line = bench.result_line(m, m.cell(CELL), rec, True)
    assert not FROM_THE_RECORDER & set(line["metrics"])
    assert {FAILED, AUCTION, BUILDS} <= set(line["metrics"]) and len(line["metrics"]) >= 25
