"""The eleven per-layer metrics that read the program's CPU ledger
(``perfbench/threadcpu.py`` over rows ``proc.cpu`` / ``proc.cpu.process``) and
its ``store.delete`` tally: the arithmetic on a record whose rows are given,
the readings on traced toy runs of their cells through ``run_cell`` (counts on
the CPU, never a device number), nothing for the plain reference and nothing
for a program whose recorder has no such rows (a parent commit with these
files laid over it).

``test_perfbench_pop_window.py`` pins ``pop_window_ms_p50``'s two entries to
the end of ``per_layer``, and ``test_perfbench_unschedulable_cell.py`` that
the unschedulable cell reports the spread cell's metrics but for a named few,
where ``store_delete_us_per_pod.backlog`` is now one more; the three cases are
deselected in ``pytest.ini`` and run here on the manifest with the eleven
entries taken off."""

import json
import math
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

from perfbench import harness, programtrace, reduce, threadcpu  # noqa: E402
from perfbench import run as bench  # noqa: E402
from perfbench.manifest import Manifest  # noqa: E402

import test_perfbench_pop_window as pop_window  # noqa: E402
import test_perfbench_unschedulable_cell as unschedulable  # noqa: E402

DOC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CLOSED = ["perf5k-basic-closed256", "perf5k-spread-closed256",
          "perf5k-antiaffinity-closed256-live2000", "perf5k-unschedulable-closed256-live5000"]
STEADY = ["perf5k-basic-steady"]
DELETING = CLOSED[2:]
INTERP = "interpreter (CPython collector)"
SHARES = ["cpu_share_" + g + ".backlog"
          for g in ("client", "lane", "commit", "informer", "watch_dispatch", "native")]
# name -> (unit, source, layer, moves, cells)
ENTRIES = {
    "host_cpu_us_per_pod.backlog": ("us/pod", "program_counter", INTERP, "bound_pods_per_s", CLOSED),
    "host_cpu_us_per_pod.steady": ("us/pod", "program_counter", INTERP, "bind_p50_s", STEADY),
    **{s: ("%", "program_counter", INTERP, "bound_pods_per_s", CLOSED) for s in SHARES},
    "interp_wait_ms_p50.backlog": ("ms", "program_counter", INTERP, "bound_pods_per_s", CLOSED),
    "interp_wait_ms_p50.steady": ("ms", "program_counter", INTERP, "bind_p50_s", STEADY),
    "store_delete_us_per_pod.backlog": ("us/pod", "program_span",
                                        "client write (api/store.py create, journal)",
                                        "bound_pods_per_s", DELETING),
}
NAMES = list(ENTRIES)
# the toy cells: the plain closed loop, the open loop, a mix that deletes
CELLS = ["perf5k-basic-closed256", "perf5k-basic-steady", "perf5k-antiaffinity-closed256-live2000"]
FAST = {"replay_walk": [8], "replay_cap_s": 20.0, "drain_s": 30.0}


def _without_mine(doc):
    doc = json.loads(json.dumps(doc))
    assert [x["name"] for x in doc["per_layer"][-len(NAMES):]] == NAMES
    doc["per_layer"] = doc["per_layer"][:-len(NAMES)]
    return doc


def test_per_layer_gained_the_eleven_entries_at_its_end_as_listed_here():
    for name, (unit, source, layer, moves, cells) in ENTRIES.items():
        (entry,) = [x for x in DOC["per_layer"] if x["name"] == name]
        assert entry == {"name": name, "unit": unit, "better": "lower", "source": source,
                         "layer": layer, "moves": moves, "workloads": cells}
        m = Manifest()
        for cell in cells:
            assert name in {x["name"] for x in m.metrics_for(cell, "per_layer")}


@pytest.mark.parametrize("case", [
    "test_per_layer_gained_the_two_entries_at_its_end_and_nothing_else_changed",
    "test_what_the_cell_of_pr_35_pinned_holds_of_the_manifest_before_this_pr",
])
def test_what_the_pop_window_cases_pinned_holds_of_the_manifest_before_the_eleven(case, monkeypatch):
    monkeypatch.setattr(pop_window, "DOC", _without_mine(DOC))
    fn = getattr(pop_window, case)
    fn(monkeypatch) if "monkeypatch" in fn.__code__.co_varnames else fn()


def test_the_unschedulable_cells_metrics_hold_of_the_manifest_before_the_eleven(monkeypatch):
    listed = unschedulable.per_layer
    monkeypatch.setattr(unschedulable, "DOC", _without_mine(DOC))
    monkeypatch.setattr(unschedulable, "per_layer",
                        lambda cell: [n for n in listed(cell) if n not in ENTRIES])
    unschedulable.test_the_new_metrics_are_listed_where_their_readers_find_something_to_read()


# -- the arithmetic, on rows that are given ------------------------------------

THREADS = {1: "scheduler", 2: "perfbench-create-0", 3: "bind-wave", 4: "commit-subwave_2",
           5: "informer-Pod", 6: "watch-dispatch-3", 7: "event-broadcaster"}


def wake(start, end, cpu0, cpu1):
    return {"name": "proc.cpu.process", "start": start, "end": end, "cpu0": cpu0, "cpu1": cpu1,
            "n": 7, "a0": 0.0, "a1": 0.0, "thread": 9}


def cpu(t, key, cpu0, cpu1):
    return {"name": "proc.cpu", "start": t, "end": t, "cpu0": cpu0, "cpu1": cpu1, "n": key,
            "a0": 0.0, "a1": 0.0, "thread": 100 + key}


def tally(name, t, n, secs):
    return {"name": name, "start": t, "end": t + 0.1, "cpu0": None, "cpu1": None, "n": n,
            "a0": secs, "a1": 0.0, "thread": 102}


SPANS = [
    cpu(100.0, 1, 0.0, 2.0),                      # at the first wake: before the span
    wake(99.9, 100.0, 0.0, 10.0),
    cpu(100.25, 1, 2.0, 2.1), cpu(100.25, 2, 1.0, 1.2), cpu(100.25, 5, 0.0, 0.05),
    wake(100.24, 100.25, 10.0, 10.4),
    cpu(100.5, 1, 2.1, 2.3), cpu(100.5, 3, 0.5, 0.55), cpu(100.5, 4, 0.0, 0.05),
    cpu(100.5, 6, 3.0, 3.05), cpu(100.5, 7, 0.2, 0.25),
    wake(100.45, 100.5, 10.4, 10.9),              # 50 ms late
    tally("store.delete", 100.1, 4, 0.002), tally("store.delete", 100.3, 2, 0.001),
    tally("store.create", 100.1, 9, 0.02),
]


@pytest.fixture
def given(monkeypatch):
    from kubernetes_tpu.utils import trace

    monkeypatch.setattr(trace, "thread_names", lambda: dict(THREADS))
    return {"kind": "backlog", "_programtrace": {"edges": (99.0, 101.0), "spans": list(SPANS)},
            "bind_log": [(100.0, "a"), (100.1, "b"), (100.3, "c"), (100.6, "d")]}


def test_the_arithmetic_on_rows_that_are_given(given):
    m = Manifest()

    def read(name, rec=given):
        return m.reader("per_layer", name)(rec)

    # the process ran 0.9 s from the first wake to the last: lane 0.3, client 0.2,
    # commit 0.1, informer 0.05, fan-out 0.05, the broadcaster 0.05 (no group), the rest 0.15
    want = {"client": 0.2, "lane": 0.3, "commit": 0.1, "informer": 0.05,
            "watch_dispatch": 0.05, "native": 0.15}
    for g, secs in want.items():
        assert read(f"cpu_share_{g}.backlog") == pytest.approx(100.0 * secs / 0.9)
    assert sum(read(s) for s in SHARES) == pytest.approx(100.0 * 0.85 / 0.9)
    # 0.9 s over the two pods seen bound after the first wake, up to the last
    assert read("host_cpu_us_per_pod.backlog") == pytest.approx(450_000.0)
    assert read("interp_wait_ms_p50.backlog") == pytest.approx(50.0)
    assert read("store_delete_us_per_pod.backlog") == pytest.approx(500.0)
    steady = dict(given, kind="open_loop",
                  due=[("ns", f"p{i}", None, t, t) for i, t in enumerate((100.2, 100.4, 100.5))])
    steady.pop("_threadcpu", None)
    assert read("host_cpu_us_per_pod.steady", steady) == pytest.approx(300_000.0)
    assert read("interp_wait_ms_p50.steady", steady) == pytest.approx(50.0)


def test_a_record_without_the_rows_reads_none(given):
    m = Manifest()
    stripped = dict(given, _programtrace={"edges": (99.0, 101.0), "spans": [
        s for s in SPANS if s["name"] not in ("proc.cpu", "proc.cpu.process", "store.delete")]})
    for name in NAMES:
        assert m.reader("per_layer", name)(stripped) is None
        assert m.reader("per_layer", name)({"kind": "backlog", "_programtrace": None}) is None


# -- toy runs ------------------------------------------------------------------


def run(cell, system="served"):
    """One toy record whose recorder rows are read at once: the next run in
    this process reuses pod keys, and the rings move on."""
    m = Manifest()
    # a 2 s window that held no whole wave (a build inside it): the next run finds it built
    for _ in range(3):
        rec = harness.run_cell(m, m.cell(cell), 2**31 + 37, 2.0, True, True, system_name=system,
                               t_start=time.perf_counter(), overrides=FAST)
        if reduce.edges(rec) is not None:
            break
    threadcpu.load(rec)
    return rec


@pytest.fixture(scope="module")
def records():
    """The module's toy records.  The ledger runs while a Scheduler does; one that
    another test in this process left started is stopped meanwhile, so that the
    reference's run is the reference's alone."""
    from kubernetes_tpu.utils import trace

    others = set(trace._ledger_owners)
    for o in others:
        trace.stop_cpu_ledger(o)
    try:
        out = {cell: run(cell) for cell in CELLS}
        out["reference"] = run("perf5k-basic-steady", system="reference")
    finally:
        for o in others:
            trace.start_cpu_ledger(o)
    return out


def listed(cell):
    return [n for n in NAMES if cell in ENTRIES[n][4]]


@pytest.mark.parametrize("cell", CELLS)
def test_each_reader_of_a_toy_run_reads_within_its_range(records, cell):
    rec, m = records[cell], Manifest()
    values = {n: m.reader("per_layer", n)(rec) for n in listed(cell)}
    for name, value in values.items():
        if name == "store_delete_us_per_pod.backlog":
            continue
        assert value is not None and math.isfinite(value), name
        if name.startswith("cpu_share_"):
            assert 0.0 <= value <= 100.0, name
        elif name.startswith("host_cpu_"):
            assert value > 0.0
        else:
            assert value >= 0.0
    if cell in CLOSED:
        assert sum(values[s] for s in SHARES) <= 100.0 + 1e-9
        assert values["cpu_share_lane.backlog"] > 0.0
        assert values["cpu_share_client.backlog"] > 0.0
    # a delete tally where the mix deletes, and none where it does not
    tallies = programtrace.spans_named(rec, ("store.delete",))
    deletes = rec["params"].get("live_pods") is not None
    assert bool(tallies) == deletes
    if deletes:
        assert values["store_delete_us_per_pod.backlog"] > 0.0
    assert m.reader("per_layer", "store_delete_us_per_pod.backlog")(rec) is None or deletes


def _thread_cpu(rec, span_name):
    """(CPU over the ledger's span, ident, its clock at the first and at the
    last read) of the thread that writes `span_name`."""
    led, spans = threadcpu.load(rec), programtrace.load(rec)["spans"]
    (ident,) = {s["thread"] for s in spans if s["name"] == span_name}
    rows = sorted((s for s in spans if s["name"] == "proc.cpu" and s["thread"] == ident
                   and led["t0"] < s["start"] <= led["t1"]), key=lambda s: s["start"])
    return (sum(s["cpu1"] - s["cpu0"] for s in rows), ident,
            rows[0]["cpu0"] if rows else 0.0, rows[-1]["cpu1"] if rows else 0.0)


def _spans_cpu(rec, names, ident, lo, hi):
    """CPU of the thread's spans that lie between its clock's reads `lo` and
    `hi`: a wake reads a thread's clock a little after the wake's own stamp,
    so the bounds are the thread's clock, not the wall."""
    return sum(s["cpu1"] - s["cpu0"] for s in programtrace.load(rec)["spans"]
               if s["name"] in names and s["thread"] == ident and s["end"] is not None
               and s["cpu0"] is not None and lo <= s["cpu0"] and s["cpu1"] <= hi)


@pytest.mark.parametrize("cell", CELLS)
def test_a_threads_cpu_holds_the_cpu_of_its_own_spans(records, cell):
    rec = records[cell]
    names = {n for n, _ in threadcpu.load(rec)["threads"].values()}
    assert {"scheduler", "event-broadcaster"} <= names
    # the lane: its cycles and the pops between them, which do not overlap
    lane, ident, lo, hi = _thread_cpu(rec, "sched.cycle")
    assert lane == pytest.approx(hi - lo)                 # every advance on a row
    spans = _spans_cpu(rec, ("sched.cycle", "sched.pop_wait"), ident, lo, hi)
    assert 0.0 < spans <= lane + 1e-6
    # the event broadcaster: its flushes
    flusher, ident, lo, hi = _thread_cpu(rec, "events.flush")
    assert _spans_cpu(rec, ("events.flush",), ident, lo, hi) <= flusher + 1e-6


@pytest.mark.parametrize("cell", CELLS)
def test_the_traced_line_carries_the_new_metrics_and_leaves_them_out_without_the_rows(records,
                                                                                        cell):
    m = Manifest()
    line = bench.result_line(m, m.cell(cell), records[cell], True)
    assert line["correct"] is True and line["failed"] == 0
    assert set(listed(cell)) <= set(line["metrics"])
    # the parent's recorder: none of the ledger's rows and no delete tally
    rec = dict(records[cell])
    pt = dict(programtrace.load(rec))
    pt["spans"] = [s for s in pt["spans"]
                   if s["name"] not in ("proc.cpu", "proc.cpu.process", "proc.stall",
                                        "store.delete")]
    rec["_programtrace"] = pt
    rec.pop("_threadcpu")
    stripped = bench.result_line(m, m.cell(cell), rec, True)
    assert not set(NAMES) & set(stripped["metrics"])
    assert set(stripped["metrics"]) == set(line["metrics"]) - set(NAMES)


@pytest.mark.parametrize("name", NAMES)
def test_each_reader_gives_none_for_the_reference(records, name):
    assert Manifest().reader("per_layer", name)(records["reference"]) is None
