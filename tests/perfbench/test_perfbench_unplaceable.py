"""Pods that are not meant to bind.  A role that no node of the empty cluster
admits, by the deployment's rules alone, is created without a wait, left
pending and held to staying unbound: ``sched-perf-5000n-unschedulable`` under
``closed256-live5000``, files no entry of BENCHMARK.json names yet.  The whole
reference comes out correct there with its pending pods still pending, the
control ``unplaceable`` not correct by its own number, the served program runs
it at toy size without waiting for what cannot come, and two faults planted
under the served path come out each by its number.  The listed cells' roles
are all placeable, so every wait there counts what it counted (counts on the
CPU, toy size)."""

import copy
import json
import os
import sys
import time

import pytest
import yaml

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

from perfbench import harness, reference  # noqa: E402
from perfbench.deployment import Deployment  # noqa: E402
from perfbench.manifest import Manifest, ManifestError  # noqa: E402

import test_perfbench_deletions as pins  # noqa: E402

DOC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CONFIG, MIX = "sched-perf-5000n-unschedulable", "closed256-live5000"
CELL = {"name": "perf5k-unschedulable-closed256-live5000", "config": CONFIG, "traffic": MIX,
        "chips": 1}
FAST = {"replay_walk": [8], "replay_cap_s": 2.0, "drain_s": 6.0}
# under the suite's load the served toy builds its executables slowly (test_perfbench_spread_cell)
STEADY = dict(FAST, replay_cap_s=20.0, drain_s=30.0)
ALWAYS = ["unbound", "bound_twice", "stray_binds", "overcommitted_nodes", "journal_diff",
          "rv_regressions"]
CHECKS = ALWAYS + ["bound_unplaceable", "deletions_lost", "deletions_unasked"]
LISTED = [c["name"] for c in DOC["configs"]]


def run(seed, system="reference", control=None, plant=None, manifest=None, overrides=FAST):
    return harness.run_cell(
        manifest or Manifest(), CELL, seed, 3.0, False, True, system_name=system,
        control=control, t_start=time.perf_counter(), plant=plant, overrides=overrides,
    )


def failing(verdict):
    return {k for k, (v, lim) in verdict["checks"].items() if v > lim}


# -- a role is placeable or it is not, by the rules alone ---------------------------

@pytest.mark.parametrize("toy", [True, False])
@pytest.mark.parametrize("config", LISTED)
def test_every_role_of_a_listed_configuration_is_placeable(config, toy):
    dep = Deployment(Manifest().config(config), toy=toy)
    assert reference.unplaceable_roles(dep) == frozenset()
    assert dep.skip_wait == {"init": False, "measure": False}
    ledger = reference.Ledger(dep.nodes(), dep.templates)
    assert all(ledger.placeable(role, dep.namespace_of(role)) for role in dep.templates)


@pytest.mark.parametrize("toy", [True, False])
def test_no_node_holds_the_new_deployments_init_pods(toy):
    dep = Deployment(Manifest().config(CONFIG), toy=toy)
    assert reference.unplaceable_roles(dep) == frozenset({"init"})
    assert dep.skip_wait == {"init": True, "measure": False}
    ledger = reference.Ledger(dep.nodes(), dep.templates)
    assert ledger.placeable("measure", "team-0") and not ledger.placeable("init", "team-0")


def test_placeable_is_asked_of_the_cluster_as_the_ledger_holds_it():
    # a full cluster admits nobody: the answer is the rules' own, at the ledger's state
    dep = Deployment(Manifest().config("sched-perf-5000n"), toy=True)
    ledger = reference.Ledger(dep.nodes()[:1], dep.templates)
    assert ledger.placeable("measure", "team-0")
    for _ in range(40):
        ledger.bind("measure", "node-0", "team-0")
    assert not ledger.placeable("measure", "team-0")


class WithoutTheFlag(Manifest):
    """The new configuration as it would be had upstream's op not said
    skipWaitToCompletion."""

    def config(self, name):
        doc = copy.deepcopy(super().config(name))
        for op in doc["test_case"]["workloadTemplate"]:
            op.pop("skipWaitToCompletion", None)
        return doc


def test_an_unplaceable_role_that_is_waited_for_stops_the_run_by_name_before_any_load(
        monkeypatch):
    made = []
    monkeypatch.setattr(harness, "make_system", lambda *a, **kw: made.append(a))
    with pytest.raises(ManifestError, match="skipWaitToCompletion") as err:
        run(1, manifest=WithoutTheFlag())
    assert "init pod" in str(err.value) and CONFIG in str(err.value)
    assert made == []           # no system was built, nothing was loaded


# -- the files ----------------------------------------------------------------------

def test_the_files_are_in_the_tree_and_no_entry_names_them_yet():
    m = Manifest()
    assert CONFIG not in {c["name"] for c in DOC["configs"]}
    assert MIX not in {w["traffic"] for w in DOC["workloads"]}
    doc = m.config(CONFIG)          # found by its name alone
    assert doc["name"] == CONFIG
    assert doc["source"].endswith("performance-config.yaml#Unschedulable/5000Nodes/2000InitPods")
    assert len(doc["source"]) <= 200
    ops = doc["test_case"]["workloadTemplate"]
    assert [op["opcode"] for op in ops] == ["createNodes", "createPods", "createPods"]
    assert ops[1]["skipWaitToCompletion"] is True and "pod-large-cpu" in ops[1]["podTemplatePath"]
    assert ops[2]["collectMetrics"] is True and "skipWaitToCompletion" not in ops[2]
    assert doc["test_case"]["workloads"][0]["params"] == {
        "initNodes": 5000, "initPods": 2000, "measurePods": 5000}
    assert set(doc["reduced"]) == {"measurePods"} and doc["capacity_pods"] == 200000
    assert set(doc["guarantees"]) == {"bound_exactly_once", "fits", "durable", "rv_monotone",
                                      "unplaceable_stay_pending"}
    basic = m.config("sched-perf-5000n")
    for key in ("store", "scheduler", "namespaces"):
        assert doc["assumed"][key] == basic["assumed"][key]
    assert doc["assumed"]["pods_complete"] and "AssignedPodDelete" in doc["assumed"]["pods_complete"]
    assert "not fetched" in doc["source_note"]


def test_the_large_pod_is_upstreams_and_not_the_ports_file_of_that_name():
    tdir = os.path.join(ROOT, "perfbench", "configs", "templates")
    with open(os.path.join(tdir, "pod-large-cpu.yaml")) as f:
        mine = yaml.safe_load(f)
    req = mine["spec"]["containers"][0]["resources"]["requests"]
    assert (str(req["cpu"]), req["memory"]) == ("9", "500Mi")
    assert "priority" not in mine["spec"] and "priorityClassName" not in mine["spec"]
    with open(os.path.join(tdir, "node-default.yaml")) as f:
        node = yaml.safe_load(f)
    assert int(str(node["status"]["capacity"]["cpu"])) < int(str(req["cpu"]))
    sources = Manifest().config(CONFIG)["pod_template_sources"]
    assert "priority: 10" in sources["templates/pod-large-cpu.yaml"]


def test_the_mix_is_closed256_live2000_at_upstreams_population():
    m = Manifest()
    mine, theirs = m.traffic(MIX), m.traffic("closed256-live2000")
    same = set(theirs) - {"what", "live_pods", "trace_seconds", "trace_seconds_why"}
    assert {k: mine[k] for k in same} == {k: theirs[k] for k in same}     # `toy` among them
    assert set(mine) == set(theirs)
    assert (mine["live_pods"], mine["trace_seconds"]) == (5000, 10.0)
    assert mine["trace_seconds"] == m.traffic("closed256")["trace_seconds"]


def test_the_mix_creates_the_pods_closed256_creates():
    gen, system = pins.drive("backlog", MIX, 160)
    first = [list(c[:2]) for c in gen.created[:160]]
    assert pins.digest(first) == pins.PARENT_PODS["closed256"], first[:3]


def test_where_every_role_is_placeable_warmup_gets_todays_pods_pod_for_pod():
    m = Manifest()
    setup = harness.Setup(m, m.cell("perf5k-basic-closed256"), True, "reference", None)
    try:
        walk = setup.dep.namespace_walk(7, 0)
        assert setup.warmup_pods(7, 32) == [
            setup.dep.pod("measure", f"warmup-{i}", next(walk)) for i in range(32)]
        assert setup.unplaceable == frozenset() and setup.n_awaited(
            [("a", "b", "init"), ("a", "c", "measure")]) == 2
    finally:
        setup.tear_down()
    setup = harness.Setup(m, CELL, True, "reference", None)
    try:
        pods = setup.warmup_pods(7, 32)
        cpu = [d["spec"]["containers"][0]["resources"]["requests"]["cpu"] for d in pods]
        assert cpu == ["100m", 9] * 32            # each template, turn by turn
        assert len({(d["metadata"]["namespace"], d["metadata"]["name"]) for d in pods}) == 64
        assert setup.n_awaited([("a", "b", "init"), ("a", "c", "measure")]) == 1
    finally:
        setup.tear_down()


# -- the proof, at toy size ---------------------------------------------------------

@pytest.fixture(scope="module")
def whole():
    return run(2**31 + 41)


def test_the_whole_reference_comes_out_correct_and_its_pending_pods_stay_pending(whole):
    v = whole["verdict"]
    assert v["correct"], v["checks"]
    assert list(v["checks"]) == CHECKS        # the rules', bound_unplaceable, the deletions' two
    assert all(pair == [0, 0] for pair in v["checks"].values())
    n_init = Manifest().config(CONFIG)["toy"]["initPods"]
    assert n_init >= 97                       # the control binds every 97th
    assert not any(name.startswith("init-") for _, name in whole["bound"])
    assert len(whole["deleted"]) > 100 and whole["drained"]
    # set-up did not wait for them and the drain ended with the last pod that can bind
    assert whole["setup_phases"]["init_pods"] < 5.0
    assert whole["t_drained"] - whole["t_off"] < whole["params"]["drain_s"] / 2


@pytest.mark.parametrize("control,numbers", [
    ("unplaceable", {"bound_unplaceable", "overcommitted_nodes"}),
    ("capacity", {"overcommitted_nodes"}),
    ("delete_lost", {"deletions_lost", "journal_diff"}),
    ("durability", {"journal_diff"}),
])
def test_a_broken_guarantee_comes_out_not_correct_by_its_own_number(control, numbers):
    v = run(2**31 + 42, control=control)["verdict"]
    assert not v["correct"] and failing(v) == numbers, v["checks"]
    if control == "unplaceable":
        # every 97th of the pending pods is bound; 9 cpu on a 4-cpu node cannot break the one
        # number without the other
        n_init = Manifest().config(CONFIG)["toy"]["initPods"]
        assert v["checks"]["bound_unplaceable"] == [n_init // 97, 0]
        assert v["checks"]["overcommitted_nodes"] == [n_init // 97, 0]
    else:
        assert v["checks"]["bound_unplaceable"] == [0, 0]
    if control == "delete_lost":
        assert v["checks"]["deletions_lost"] == v["checks"]["journal_diff"]


def test_the_control_is_known_only_where_a_role_is_unplaceable():
    m = Manifest()
    with pytest.raises(ValueError, match="unknown control"):
        harness.run_cell(m, m.cell("perf5k-basic-closed256"), 1, 3.0, False, True,
                         system_name="reference", control="unplaceable",
                         t_start=time.perf_counter(), overrides=FAST)


# -- the served program, and two faults under its timed path ------------------------

def pending_pod(system):
    pods, _ = system.store.list("Pod")
    return next(p for p in pods if p.meta.name.startswith("init-") and not p.spec.node_name)


def bind_one_pending_pod(system):
    """One pending pod bound by a direct store write, whatever the rules say."""
    pod = pending_pod(system)
    pod.spec.node_name = "node-0"
    system.store.update(pod, force=True)


def delete_one_pending_pod(system):
    """One pending pod deleted, and nobody asked."""
    pod = pending_pod(system)
    system.store.delete("Pod", pod.meta.name, pod.meta.namespace)


@pytest.fixture(scope="module")
def served():
    return run(2**31 + 43, system="served", overrides=STEADY)


def test_the_served_program_is_correct_and_leaves_the_pending_pods_pending(served):
    v = served["verdict"]
    assert v["correct"], v["checks"]
    assert list(v["checks"]) == CHECKS
    assert all(pair == [0, 0] for pair in v["checks"].values()), v["checks"]
    assert not any(name.startswith("init-") for _, name in served["bound"])
    assert len(served["deleted"]) > 0 and len(served["bind_log"]) > 100


def test_the_drain_does_not_wait_for_what_cannot_come(served):
    assert served["drained"] is True
    assert served["t_drained"] - served["t_off"] < served["params"]["drain_s"] / 3
    # the scheduler did try them: some cycle held more pods than it placed
    assert any(c.get("pods", 0) > c.get("placed", 0) for c in served["cycles"])


def test_a_pending_pod_bound_by_a_direct_store_write_is_counted():
    v = run(2**31 + 44, system="served", plant=bind_one_pending_pod, overrides=STEADY)["verdict"]
    assert not v["correct"]
    assert v["checks"]["bound_unplaceable"] == [1, 0], v["checks"]
    assert failing(v) <= {"bound_unplaceable", "overcommitted_nodes"}
    assert v["checks"]["unbound"] == [0, 0] and v["checks"]["journal_diff"] == [0, 0]


def test_a_pending_pod_deleted_unasked_is_counted_and_missed_in_the_journal():
    v = run(2**31 + 45, system="served", plant=delete_one_pending_pod,
            overrides=STEADY)["verdict"]
    assert not v["correct"]
    assert v["checks"]["deletions_unasked"] == [1, 0], v["checks"]
    assert v["checks"]["journal_diff"] == [1, 0]
    # no rule of placement is broken: the pods that live are where they may be
    assert failing(v) == {"deletions_unasked", "journal_diff"}
