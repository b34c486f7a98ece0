"""``sched-perf-5000n-podaffinity`` (upstream SchedulingPodAffinity/5000Nodes)
and its cell ``perf5k-podaffinity-closed256-live2000``.

Upstream labels every node of this case with the one zone ``zone1``, so the
term admits every node once the first pod is bound and no placement of the
listed deployment can break the rule.  Where the rule can be broken is shown
over the port's eight zones (``OverEightZones``, the same deployment with the
port's ``node-default.yaml``): the rule file ``perfbench/rules/pod_affinity.py``
on binds given by hand (a pod beside a match, the first pod's escape, a breach,
a deletion), its control not correct by ``unaffine_pods`` alone, and a bind
moved to another zone under the timed path caught.  Besides: what the rule
claims, the copies against the port's, the whole reference and the served
program correct at toy size, the new reader
``interpod_saturated_pods_share.backlog`` on rows given, and where the manifest
lists the cell (counts on the CPU; never a device number).

Ten cases of older modules state the tree before this cell was listed and are
deselected in ``pytest.ini``; each is run here on the manifest as it stood
before the cell, or restated for the four rule files."""

import copy
import json
import os
import re
import sys
import time

import pytest
import yaml

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

from perfbench import harness, rules  # noqa: E402
from perfbench.deployment import Deployment  # noqa: E402
from perfbench.manifest import Manifest, ManifestError, load_function  # noqa: E402

import test_perfbench_pop_window as pop_window  # noqa: E402
import test_perfbench_threadcpu as threadcpu  # noqa: E402
import test_perfbench_unschedulable_cell as unschedulable  # noqa: E402

DOC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
UPSTREAM = os.path.join(ROOT, "kubernetes_tpu", "perf", "config")
CONFIG, MIX = "sched-perf-5000n-podaffinity", "closed256-live2000"
CELL = "perf5k-podaffinity-closed256-live2000"
ANTI = "perf5k-antiaffinity-closed256-live2000"
NEW = "interpod_saturated_pods_share.backlog"
TEMPLATE = "pod-with-pod-affinity.yaml"
ZONE = "topology.kubernetes.io/zone"
CLOSED = ["perf5k-basic-closed256", "perf5k-spread-closed256", ANTI,
          "perf5k-unschedulable-closed256-live5000"]
# the readers the cell is added to beside every one the four closed cells share
NAMED = {"setup_compile_s", "peak_device_bytes", "builds_after_warmup",
         "solve_waves_per_cycle.backlog", "solve_wave_fallbacks_per_cycle.backlog",
         "solve_wave_steps_per_pod.backlog", "constraint_encode_ms_per_cycle.backlog",
         "interpod_term_rows_per_cycle.backlog", "cache_remove_us_per_pod.backlog",
         "store_delete_us_per_pod.backlog", "solve_roofline_interpod.backlog"}
NOT_NAMED = {"solve_roofline.backlog", "spread_rows_per_cycle.backlog",
             "queue_wake_pods_per_s.backlog", "queue_wake_us_per_pod.backlog",
             "queue_wake_walk_us_per_call.backlog", "queue_parked_pods_p50.backlog",
             "fail_branch_us_per_pod.backlog", "solve_failed_slots_share.backlog",
             "solve_auction_slots_share.backlog"}
ALWAYS = ["unbound", "bound_twice", "stray_binds", "overcommitted_nodes", "journal_diff",
          "rv_regressions"]
CHECKS = ALWAYS + ["unaffine_pods", "deletions_lost", "deletions_unasked"]
FAST = {"replay_walk": [8], "replay_cap_s": 2.0, "drain_s": 6.0}
# what only a chip holds: no CPU run reads these
DEVICE_ONLY = {"solve_device_us_per_pod.backlog", "device_idle_share.backlog",
               "peak_device_bytes", "solve_roofline_interpod.backlog"}


def _upto(entries, name):
    return entries[:[x["name"] for x in entries].index(name)]


def before_the_cell(doc):
    """The manifest as it stood before the cell was listed: each list cut at
    this cell's entry, and every list of cells held to the cells before it.
    The manifest grows only at the ends of its lists, so this holds whatever
    entries come after."""
    doc = json.loads(json.dumps(doc))
    doc["configs"] = _upto(doc["configs"], CONFIG)
    doc["workloads"] = _upto(doc["workloads"], CELL)
    doc["per_layer"] = _upto(doc["per_layer"], NEW)
    cells = {w["name"] for w in doc["workloads"]}
    for x in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in x:
            x["workloads"] = [c for c in x["workloads"] if c in cells]
    return doc


def entry(group, name):
    found = [x for x in DOC[group] if x["name"] == name]
    assert len(found) == 1, (group, name)
    return found[0]


def gained():
    """The metrics, of those listed up to this cell's reader, that list the cell."""
    listed = DOC["end_to_end"] + _upto(DOC["per_layer"], NEW)
    return [x["name"] for x in listed if CELL in x.get("workloads", [])]


def failing(verdict):
    return {k for k, (v, lim) in verdict["checks"].items() if v > lim}


def rule_class():
    return load_function(os.path.join(rules.RULES_DIR, "pod_affinity.py"), "Rule")


class OverEightZones(Manifest):
    """The configuration over the port's node-default.yaml, zone-(i mod 8):
    eight zones, where a pod in the wrong one breaks the rule."""

    def config(self, name):
        doc = copy.deepcopy(super().config(name))
        if name == CONFIG:
            doc["node_template"] = "templates/node-default.yaml"
            doc["capacity_pods"], doc["toy"]["capacity_pods"] = 25000, 1280
        return doc


def toy(manifest=None):
    return Deployment((manifest or Manifest()).config(CONFIG), toy=True)


def run(seed, system="reference", control=None, plant=None, seconds=3.0, manifest=None):
    m = manifest or Manifest()
    return harness.run_cell(m, m.cell(CELL), seed, seconds, False, True, system_name=system,
                            control=control, t_start=time.perf_counter(), plant=plant,
                            overrides=FAST)


# -- the rule, on binds given by hand -------------------------------------------------

@pytest.fixture
def rule():
    dep = toy(OverEightZones())
    return rule_class()(dep.nodes(), dep.templates)


def test_on_the_one_zone_every_node_admits_every_pod_once_the_first_is_bound():
    dep = toy()
    nodes = dep.nodes()
    names = [n["metadata"]["name"] for n in nodes]
    assert {n["metadata"]["labels"][ZONE] for n in nodes} == {"zone1"}
    r = rule_class()(nodes, dep.templates)
    assert all(r.admits("init", name, "sched-0") for name in names)     # the escape
    r.bind("init", "node-0", "sched-0")
    assert all(r.admits(role, name, ns) for name in names
               for role, ns in (("init", "sched-0"), ("measure", "sched-1")))
    for name in names:
        r.bind("measure", name, "sched-1")
    assert r.checks() == {"unaffine_pods": [0, 0]}


def test_a_pod_lands_beside_a_match_the_first_one_escapes_and_a_breach_is_counted(rule):
    # node-i is in zone-(i mod 8); nothing is bound: the escape admits any node
    assert rule.admits("init", "node-3", "sched-0")
    rule.bind("init", "node-0", "sched-0")
    # the term lists both namespaces: a measured pod lands beside an init pod
    assert rule.admits("measure", "node-8", "sched-1")
    assert rule.admits("measure", "node-0", "sched-1")
    # zone-1 holds no match, and the escape is closed while one lives anywhere
    assert not rule.admits("measure", "node-1", "sched-1")
    rule.bind("measure", "node-8", "sched-1")
    assert rule.checks() == {"unaffine_pods": [0, 0]}
    rule.bind("measure", "node-1", "sched-1")
    assert rule.checks() == {"unaffine_pods": [1, 0]}
    # the breach made zone-1 a domain with a match: the next pod there is fine
    rule.bind("measure", "node-9", "sched-1")
    assert rule.checks() == {"unaffine_pods": [1, 0]}


def test_a_deletion_breaks_no_bound_pod_and_the_escape_reopens_when_none_lives(rule):
    rule.bind("init", "node-0", "sched-0")
    rule.bind("measure", "node-8", "sched-1")
    rule.unbind("init", "node-0", "sched-0")        # IgnoredDuringExecution
    assert rule.checks() == {"unaffine_pods": [0, 0]}
    assert rule.admits("measure", "node-16", "sched-1")
    assert not rule.admits("measure", "node-2", "sched-1")
    rule.unbind("measure", "node-8", "sched-1")
    assert rule.admits("measure", "node-2", "sched-1")     # the first pod of a new group
    # a pod outside the term's namespaces does not match its own term: no escape
    assert not rule.admits("measure", "node-2", "team-0")
    assert rule.checks() == {"unaffine_pods": [0, 0]}


def test_a_node_without_the_key_admits_nothing():
    dep = toy(OverEightZones())
    nodes = dep.nodes()
    nodes[5]["metadata"]["labels"] = {}
    r = rule_class()(nodes, dep.templates)
    assert not r.admits("init", "node-5", "sched-0")
    assert r.admits("init", "node-6", "sched-0")


def test_the_control_picks_from_every_node(rule):
    names = [n["metadata"]["name"] for n in toy().nodes()]
    assert rule.control_nodes(names, "measure") is None
    assert rule_class().control == "affinity" and rule_class().held == "every_bind"


def test_claims_refuses_match_expressions_and_a_namespace_selector():
    Rule = rule_class()
    term = toy().templates["measure"]["spec"]["affinity"]["podAffinity"][
        "requiredDuringSchedulingIgnoredDuringExecution"][0]
    assert Rule.claims("podAffinity", term)
    assert not Rule.claims("podAntiAffinity", term)
    assert not Rule.claims("podAffinity", dict(term, namespaceSelector={}))
    exprs = {"matchExpressions": [{"key": "color", "operator": "In", "values": ["blue"]}]}
    assert not Rule.claims("podAffinity", dict(term, labelSelector=exprs))


# what test_perfbench_rules.py's deselected cases pinned, as it holds with four files: the
# guard still refuses, by name, a podAffinity term no file reads
UNCLAIMED_AFFINITY = {
    "match_expressions": {"labelSelector": {"matchExpressions": [
        {"key": "color", "operator": "In", "values": ["green"]}]},
        "topologyKey": "kubernetes.io/hostname"},
    "namespace_selector": {"labelSelector": {"matchLabels": {"color": "green"}},
                           "topologyKey": "kubernetes.io/hostname", "namespaceSelector": {}},
}


@pytest.mark.parametrize("case", sorted(UNCLAIMED_AFFINITY))
def test_a_pod_affinity_term_no_rule_claims_is_refused_by_name(case):
    dep = Deployment(Manifest().config("sched-perf-5000n"))
    template = copy.deepcopy(dep.templates["measure"])
    template["spec"]["affinity"] = {"podAffinity": {
        "requiredDuringSchedulingIgnoredDuringExecution": [UNCLAIMED_AFFINITY[case]]}}
    with pytest.raises(ManifestError, match=re.escape("podAffinity on kubernetes.io/hostname")):
        rules.require_claimed({"init": dep.templates["init"], "measure": template})


def test_the_four_rules_are_one_file_each():
    files = sorted(f for f in os.listdir(rules.RULES_DIR)
                   if f.endswith(".py") and not f.startswith("_"))
    assert files == ["capacity.py", "pod_affinity.py", "pod_anti_affinity.py", "zone_spread.py"]
    assert [c.control for c in rules.rule_classes()] == [
        "capacity", "affinity", "antiaffinity", "skew"]


# -- the files ---------------------------------------------------------------------

def test_the_template_is_a_byte_copy_of_the_ports():
    with open(os.path.join(ROOT, "perfbench", "configs", "templates", TEMPLATE), "rb") as f:
        mine = f.read()
    with open(os.path.join(UPSTREAM, TEMPLATE), "rb") as f:
        assert mine == f.read()
    term = yaml.safe_load(mine)["spec"]["affinity"]["podAffinity"][
        "requiredDuringSchedulingIgnoredDuringExecution"][0]
    assert term == {"labelSelector": {"matchLabels": {"color": "blue"}},
                    "topologyKey": ZONE, "namespaces": ["sched-1", "sched-0"]}


def test_the_test_case_equals_the_ports_and_the_namespaces_are_its_ops():
    doc = Manifest().config(CONFIG)
    with open(os.path.join(UPSTREAM, "performance-config.yaml")) as f:
        cases = {c["name"]: c for c in yaml.safe_load(f)}
    mine, theirs = doc["test_case"], cases["SchedulingPodAffinity"]
    assert mine["name"] == theirs["name"]
    assert mine["workloadTemplate"] == theirs["workloadTemplate"]
    assert mine["defaultPodTemplatePath"] == theirs["defaultPodTemplatePath"] == TEMPLATE
    assert mine["workloads"] == [w for w in theirs["workloads"] if w["name"] == "5000Nodes"]
    assert mine["workloads"][0]["params"] == {
        "initNodes": 5000, "initPods": 1000, "measurePods": 1000}
    ops = [op for op in mine["workloadTemplate"] if op["opcode"] == "createPods"]
    assert doc["assumed"]["role_namespaces"] == {
        "init": [ops[0]["namespace"]], "measure": [ops[1]["namespace"]]}


def test_the_configuration_labels_one_zone_as_upstream_and_states_the_rule():
    doc = Manifest().config(CONFIG)
    anti = Manifest().config("sched-perf-5000n-antiaffinity")
    assert doc["capacity_pods"] == 200000 and doc["max_fill_share"] == 0.75
    assert doc["toy"] == {"initNodes": 256, "initPods": 24, "capacity_pods": 10240}
    assert set(doc["guarantees"]) == set(anti["guarantees"]) - {"anti_affinity"} | {"pod_affinity"}
    assert set(doc["reduced"]) == {"measurePods"}
    for key in ("store", "scheduler", "namespaces", "role_namespaces", "pods_complete"):
        assert key in doc["assumed"]
    assert doc["node_template"] == "templates/node-zone1.yaml"
    assert "zone1" in doc["assumed"]["node_zone_label"]
    assert "recalled, not fetched" in doc["assumed"]["why_zone"]
    dep = Deployment(doc)
    zones = {n["metadata"]["labels"][ZONE] for n in dep.nodes()}
    assert zones == {"zone1"} and dep.n_nodes * 40 == doc["capacity_pods"]
    rules.require_claimed(dep.templates)


def test_the_one_zone_node_template_is_the_ports_but_for_the_zone():
    with open(os.path.join(ROOT, "perfbench", "configs", "templates", "node-zone1.yaml")) as f:
        mine = yaml.safe_load(f)
    with open(os.path.join(UPSTREAM, "node-default.yaml")) as f:
        theirs = yaml.safe_load(f)
    theirs["metadata"]["labels"][ZONE] = "zone1"
    assert mine == theirs


# -- the manifest --------------------------------------------------------------------

def test_the_cell_is_listed_where_named_and_nowhere_else():
    doc = before_the_cell(DOC)
    shared = {x["name"] for x in doc["per_layer"] if set(CLOSED) <= set(x["workloads"])}
    assert set(gained()) == {"bound_pods_per_s"} | shared | NAMED
    assert not set(gained()) & NOT_NAMED
    assert not [n for n in gained() if n.endswith(".steady")]


def test_the_new_entries_read_as_listed():
    assert entry("configs", CONFIG) == {
        "name": CONFIG,
        "source": "https://github.com/kubernetes/kubernetes/blob/v1.27.0/test/integration/"
                  "scheduler_perf/config/performance-config.yaml#SchedulingPodAffinity/5000Nodes",
        "file": "perfbench/configs/" + CONFIG + ".json", "reduced": ["measurePods"],
        "why": "upstream SchedulingPodAffinity: required zone affinity, every node in one zone "
               "(zone1); check 3 of the term path on every bind, and its first-pod escape"}
    cell = entry("workloads", CELL)
    assert {k: cell[k] for k in ("name", "config", "traffic", "chips")} == {
        "name": CELL, "config": CONFIG, "traffic": MIX, "chips": 1}
    assert len(cell["why"]) <= 200 and "remove_pod" in cell["why"]
    assert entry("per_layer", NEW) == {
        "name": NEW, "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "solve plan (ops/assign.py)", "moves": "bound_pods_per_s",
        "workloads": [CELL]}
    m = Manifest()
    assert {x["name"] for x in m.metrics_for(CELL, "end_to_end")} == {
        "bound_pods_per_s", "setup_s"}
    assert [x["name"] for x in DOC["per_layer"] if x["layer"] == "kernels"] == [
        "solve_roofline.backlog", "solve_roofline_interpod.backlog"]


# each deselected case, run on the manifest as it stood before the cell
DESELECTED = [
    (unschedulable, "test_the_cell_is_the_last_of_five_and_what_was_spelled_out_by_hand", ()),
    (unschedulable, "test_the_config_entry_is_the_last_of_four_and_names_the_file", ()),
    (threadcpu, "test_per_layer_gained_the_eleven_entries_at_its_end_as_listed_here", ()),
    (threadcpu, "test_what_the_pop_window_cases_pinned_holds_of_the_manifest_before_the_eleven",
     ("test_per_layer_gained_the_two_entries_at_its_end_and_nothing_else_changed",)),
    (threadcpu, "test_what_the_pop_window_cases_pinned_holds_of_the_manifest_before_the_eleven",
     ("test_what_the_cell_of_pr_35_pinned_holds_of_the_manifest_before_this_pr",)),
    (threadcpu, "test_the_unschedulable_cells_metrics_hold_of_the_manifest_before_the_eleven", ()),
    (pop_window, "test_the_entries_are_what_the_issue_names",
     (pop_window.BACKLOG, "bound_pods_per_s", pop_window.CLOSED)),
]


@pytest.mark.parametrize("case", range(len(DESELECTED)),
                         ids=[f"{c[1]}-{i}" for i, c in enumerate(DESELECTED)])
def test_a_deselected_case_holds_of_the_manifest_before_the_cell(case, monkeypatch):
    module, name, args = DESELECTED[case]
    before = before_the_cell(DOC)
    monkeypatch.setattr(module, "DOC", before)
    # the unschedulable cell's cases read its entries by name too (pop_window's reach them)
    monkeypatch.setattr(unschedulable, "BY_NAME", {x["name"]: x for x in before["per_layer"]})
    fn = getattr(module, name)
    if "monkeypatch" in fn.__code__.co_varnames[:fn.__code__.co_argcount]:
        args = args + (monkeypatch,)
    fn(*args)


# -- the new reader, on rows that are given -----------------------------------------

def plan_row(start, n, a0, a1=0.0):
    return {"name": "sched.plan.terms", "start": start, "end": start + 0.001, "n": n,
            "a0": a0, "a1": a1, "parent": 0, "cycle": 1}


@pytest.mark.parametrize("spans,want", [
    ([plan_row(101.0, 80, 80), plan_row(102.0, 120, 100, 1.0)], 90.0),
    ([plan_row(101.0, 80, 0), plan_row(102.0, 256, 0)], 0.0),      # anti terms: never saturated
    ([{"name": "sched.encode.terms", "start": 101.0, "end": 101.0, "n": 1, "a0": 0.0,
       "a1": 0.0}], None),                                          # the parent's recorder
], ids=["saturated", "anti", "parent"])
def test_the_reader_is_the_share_of_saturated_pods(spans, want):
    read = Manifest().reader("per_layer", NEW)
    rec = {"_programtrace": {"edges": (100.0, 110.0), "spans": spans}}
    assert read(rec) == (pytest.approx(want) if want is not None else None)
    assert read({"_programtrace": None}) is None                    # no recorder at all


# -- the proof, at toy size --------------------------------------------------------

@pytest.fixture(scope="module")
def whole():
    # the seed test_perfbench_antiaffinity.py gives every listed cell
    return run(2**31 + 35)


def test_the_whole_reference_comes_out_correct(whole):
    v = whole["verdict"]
    assert v["correct"], v["checks"]
    assert list(v["checks"]) == CHECKS
    assert all(pair == [0, 0] for pair in v["checks"].values())
    assert len(whole["deleted"]) > 0 and len(whole["gone"]) > 0


def test_the_cell_keeps_its_checks_names_limits_and_order(whole):
    """test_perfbench_antiaffinity.py's case of every listed cell, for this one
    (its EXPECTED has no entry for it)."""
    v = whole["verdict"]
    want = {n: [0, 0] for n in CHECKS}
    assert list(v["checks"]) == list(want)
    assert {k: lim for k, (_, lim) in v["checks"].items()} == {k: 0 for k in want}
    assert v["correct"] is True and all(v["checks"][k] == want[k] for k in want)
    assert len(whole["deleted"]) > 0 and "bound_unplaceable" not in v["checks"]


def test_over_eight_zones_the_control_breaks_unaffine_pods_and_nothing_else():
    v = run(2**31 + 41, control="affinity", manifest=OverEightZones())["verdict"]
    assert not v["correct"]
    assert failing(v) == {"unaffine_pods"}, v["checks"]


@pytest.fixture(scope="module")
def served():
    from perfbench import programtrace, reduce

    # a 3 s window that held no whole wave (a build under the suite's load fell inside
    # it): the next run finds the machine quieter
    for attempt in range(3):
        rec = run(2**31 + 42 + attempt, system="served")
        if reduce.edges(rec) is not None:
            break
    programtrace.load(rec)      # the recorder is read here, before any other run
    return rec


def test_the_served_program_runs_the_cell_correct_at_toy_size(served):
    v = served["verdict"]
    assert v["correct"], v["checks"]
    assert list(v["checks"]) == CHECKS and len(served["deleted"]) > 0


def test_the_served_program_plans_saturated_waves(served):
    m = Manifest()
    read = {name: m.reader("per_layer", name)(served) for name in (
        NEW, "solve_waves_per_cycle.backlog", "solve_wave_fallbacks_per_cycle.backlog",
        "cycle_pods_p50.backlog", "interpod_term_rows_per_cycle.backlog")}
    assert read[NEW] == 100.0, read
    assert read["solve_wave_fallbacks_per_cycle.backlog"] == 0.0
    assert read["interpod_term_rows_per_cycle.backlog"] == 1.0
    # a wave holds up to 32 pods where every write is saturated (the bucket's pad pods
    # ride behind the last real one); the plan without the rule ran one wave a pod
    assert read["solve_waves_per_cycle.backlog"] < read["cycle_pods_p50.backlog"] / 4


@pytest.mark.parametrize("metric", [x["name"] for x in Manifest().metrics_for(CELL, "per_layer")])
def test_every_listed_per_layer_metric_reads_on_the_toy_run(served, metric):
    value = Manifest().reader("per_layer", metric)(served)
    if metric in DEVICE_ONLY:
        assert value is None        # never a CPU number under a device metric's name
    elif metric in ("cache_remove_us_per_pod.backlog", "store_delete_us_per_pod.backlog",
                    "compiles_in_window.backlog", "builds_after_warmup"):
        assert value is None or value >= 0.0
    else:
        assert value is not None and float(value) == float(value), metric


def bind_one_elsewhere(system):
    """The first measured pod of the window bound, by the store's own wave, to
    the next node: another zone (node i is in zone-(i mod 8))."""
    store = system.store
    inner = store.update_wave
    n_nodes = system.dep.n_nodes
    moved = []

    def update_wave(kind, updates, **kw):
        if kind == "Pod" and not moved:
            for i, (name, ns, mutate) in enumerate(updates):
                if not name.startswith("pod-"):
                    continue

                def elsewhere(obj, mutate=mutate):
                    mutate(obj)
                    at = int(obj.spec.node_name.split("-")[1])
                    obj.spec.node_name = f"node-{(at + 1) % n_nodes}"

                updates = list(updates)
                updates[i] = (name, ns, elsewhere)
                moved.append(name)
                break
        return inner(kind, updates, **kw)

    store.update_wave = update_wave


def test_over_eight_zones_a_bind_moved_to_another_zone_under_the_timed_path_is_caught():
    v = run(2**31 + 47, system="served", plant=bind_one_elsewhere,
            manifest=OverEightZones())["verdict"]
    assert not v["correct"]
    assert failing(v) == {"unaffine_pods"}, v["checks"]
