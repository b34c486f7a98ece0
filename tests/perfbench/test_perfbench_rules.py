"""Each rule file alone, on hand-made binds and deletions; the list of rules
a deployment gets; and the guard: a template with a hard constraint that no
rule file claims is refused by name, before any load."""

import copy
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import harness, reference, rules  # noqa: E402
from perfbench.deployment import Deployment  # noqa: E402
from perfbench.manifest import Manifest, ManifestError, load_function  # noqa: E402

RULE_FILES = sorted(f for f in os.listdir(rules.RULES_DIR)
                    if f.endswith(".py") and not f.startswith("_"))


def rule(name):
    return load_function(os.path.join(rules.RULES_DIR, name + ".py"), "Rule")


def deployment(config):
    return Deployment(Manifest().config(config), toy=True)


def test_the_three_rules_are_one_file_each():
    assert RULE_FILES == ["capacity.py", "pod_anti_affinity.py", "zone_spread.py"]


@pytest.mark.parametrize("name", RULE_FILES)
def test_a_rule_file_imports_nothing_of_the_program_and_no_other_rule(name):
    src = open(os.path.join(rules.RULES_DIR, name)).read()
    imports = re.findall(r"^\s*(?:from|import)\s+(\S+)", src, flags=re.M)
    assert set(imports) <= {"__future__"}, imports
    cls = rule(name[:-3])
    for attr in ("control", "held", "claims", "applies", "admits", "bind", "unbind",
                 "mark_wave_end", "checks", "control_nodes"):
        assert hasattr(cls, attr), (name, attr)
    # when it is read: only the zone skew waits for whole solves
    assert cls.held == ("whole_solves" if name == "zone_spread.py" else "every_bind")


def test_reference_py_holds_no_rules_arithmetic_any_more():
    src = open(os.path.join(ROOT, "perfbench", "reference.py")).read()
    for word in ('"allocatable"', "maxSkew", "topologySpreadConstraints", "podAntiAffinity",
                 "ZONE_KEY", "def quantity", "requests"):
        assert word not in src, word
    assert not hasattr(reference, "spread_rule") and not hasattr(reference, "pod_requests")


@pytest.mark.parametrize("config,names,controls", [
    ("sched-perf-5000n", ["overcommitted_nodes"], {"capacity"}),
    ("sched-perf-5000n-spread", ["overcommitted_nodes", "max_zone_skew"], {"capacity", "skew"}),
    ("sched-perf-5000n-antiaffinity", ["overcommitted_nodes", "colocated_pods"],
     {"capacity", "antiaffinity"}),
])
def test_a_deployment_gets_the_rules_that_apply_to_its_templates(config, names, controls):
    dep = deployment(config)
    ledger = reference.Ledger(dep.nodes(), dep.templates)
    assert list(ledger.checks()) == names
    assert {r.control for r in ledger.rules} == controls
    limits = {"overcommitted_nodes": 0, "colocated_pods": 0, "max_zone_skew": 5}
    assert ledger.checks() == {n: [0, limits[n]] for n in names}


# -- capacity ---------------------------------------------------------------------

def test_capacity_a_slot_reused_before_its_delete_is_a_breach_after_it_is_not():
    dep = deployment("sched-perf-5000n")
    for order in ("after", "before"):
        cap = rule("capacity")(dep.nodes(), dep.templates)
        for _ in range(40):                     # 4 cpu / 100m: the node is full
            cap.bind("measure", "node-3", "team-0")
        assert not cap.admits("measure", "node-3", "team-0")
        assert cap.admits("measure", "node-4", "team-0")
        if order == "after":
            cap.unbind("measure", "node-3", "team-0")
            assert cap.admits("measure", "node-3", "team-0")
        cap.bind("measure", "node-3", "team-1")
        if order == "before":
            cap.unbind("measure", "node-3", "team-0")      # too late: the bind came first
        assert cap.checks() == {"overcommitted_nodes": [int(order == "before"), 0]}


def test_capacity_counts_a_node_once_however_often_it_is_over():
    dep = deployment("sched-perf-5000n")
    cap = rule("capacity")(dep.nodes(), dep.templates)
    for _ in range(45):
        cap.bind("measure", "node-0", "team-0")
    for _ in range(41):
        cap.bind("init", "node-9", "team-0")
    assert cap.checks()["overcommitted_nodes"] == [2, 0]
    assert cap.control_nodes([f"node-{i}" for i in range(5000)], "measure") == \
        [f"node-{i}" for i in range(5)]


# -- zone spread ------------------------------------------------------------------

def test_zone_spread_a_deleted_pod_leaves_its_zones_count():
    dep = deployment("sched-perf-5000n-spread")
    z = rule("zone_spread")(dep.nodes(), dep.templates)
    for _ in range(5):
        z.bind("measure", "node-0", "team-0")          # zone 0 of team-0: at the limit
    assert not z.admits("measure", "node-8", "team-0")
    z.unbind("measure", "node-0", "team-0")
    assert z.admits("measure", "node-8", "team-0")
    z.bind("init", "node-0", "team-0")                 # not counted: pod-default has no label
    assert z.admits("measure", "node-8", "team-0")
    z.mark_wave_end()
    assert z.checks() == {"max_zone_skew": [4, 5]}
    assert z.control_nodes([f"node-{i}" for i in range(16)], "measure") == ["node-0", "node-8"]
    assert z.control_nodes([f"node-{i}" for i in range(16)], "init") is None


# -- pod anti-affinity ------------------------------------------------------------

def anti(templates=None):
    dep = deployment("sched-perf-5000n-antiaffinity")
    return rule("pod_anti_affinity")(dep.nodes(), templates or dep.templates), dep


def test_anti_affinity_holds_across_the_two_namespaces_and_not_across_a_third():
    a, dep = anti()
    a.bind("init", "node-1", "sched-0")
    assert not a.admits("measure", "node-1", "sched-1")     # the other listed namespace
    assert not a.admits("init", "node-1", "sched-0")        # its own
    assert a.admits("measure", "node-2", "sched-1")         # another node
    a.bind("measure", "node-1", "sched-1")
    assert a.checks() == {"colocated_pods": [1, 0]}
    # a green pod of a third namespace that carries no term: the term lists
    # sched-1 and sched-0 only, so it neither repels nor is repelled
    plain = copy.deepcopy(dep.templates["init"])
    del plain["spec"]["affinity"]
    a, _ = anti({"init": plain, "measure": dep.templates["measure"]})
    a.bind("init", "node-1", "elsewhere")
    assert a.admits("measure", "node-1", "sched-1")
    a.bind("measure", "node-1", "sched-1")
    assert a.admits("init", "node-1", "elsewhere")
    a.bind("init", "node-1", "elsewhere")
    assert a.checks() == {"colocated_pods": [0, 0]}
    assert not a.admits("init", "node-1", "sched-0")        # the same pod in a listed one


def test_anti_affinity_counts_the_existing_pods_terms_against_the_incomer():
    # the incomer carries no term of its own; the pod that is there repels it
    _, dep = anti()
    plain = copy.deepcopy(dep.templates["measure"])
    del plain["spec"]["affinity"]
    for first, second in (("init", "measure"), ("measure", "init")):
        a, _ = anti({"init": dep.templates["init"], "measure": plain})
        a.bind(first, "node-7", "sched-0")
        assert not a.admits(second, "node-7", "sched-1"), (first, second)
        a.bind(second, "node-7", "sched-1")
        assert a.checks() == {"colocated_pods": [1, 0]}
    # two pods without a term repel nobody
    a, _ = anti({"init": dep.templates["init"], "measure": plain})
    a.bind("measure", "node-7", "sched-1")
    a.bind("measure", "node-7", "sched-1")
    assert a.checks() == {"colocated_pods": [0, 0]}


def test_anti_affinity_a_node_is_free_from_its_pods_deletion_on_and_not_before():
    a, _ = anti()
    a.bind("init", "node-5", "sched-0")
    a.unbind("init", "node-5", "sched-0")
    assert a.admits("measure", "node-5", "sched-1")
    a.bind("measure", "node-5", "sched-1")                  # after the delete: no breach
    a.bind("measure", "node-5", "sched-1")                  # before the next delete: one
    a.unbind("measure", "node-5", "sched-1")
    assert a.checks() == {"colocated_pods": [1, 0]}
    assert not a.admits("init", "node-5", "sched-0")        # one of the two still lives


def test_anti_affinity_a_term_without_namespaces_means_the_owners_own():
    _, dep = anti()
    own = copy.deepcopy(dep.templates["measure"])
    del own["spec"]["affinity"]["podAntiAffinity"][
        "requiredDuringSchedulingIgnoredDuringExecution"][0]["namespaces"]
    a, _ = anti({"init": own, "measure": own})
    a.bind("measure", "node-1", "sched-0")
    assert not a.admits("measure", "node-1", "sched-0")
    assert a.admits("measure", "node-1", "sched-1")


def test_anti_affinity_over_a_label_key_makes_every_node_with_the_value_one_domain():
    _, dep = anti()
    zonal = copy.deepcopy(dep.templates["measure"])
    zonal["spec"]["affinity"]["podAntiAffinity"][
        "requiredDuringSchedulingIgnoredDuringExecution"][0]["topologyKey"] = \
        "topology.kubernetes.io/zone"
    a, _ = anti({"init": zonal, "measure": zonal})
    a.bind("measure", "node-0", "sched-1")                  # zone 0
    assert not a.admits("measure", "node-8", "sched-1")     # zone 0 again
    assert a.admits("measure", "node-1", "sched-1")


# -- the guard --------------------------------------------------------------------

TERM = {"labelSelector": {"matchLabels": {"color": "green"}},
        "topologyKey": "kubernetes.io/hostname"}
UNCLAIMED = {
    "nodeSelector": ({"nodeSelector": {"disk": "ssd"}}, "nodeSelector"),
    "nodeAffinity": ({"affinity": {"nodeAffinity": {
        "requiredDuringSchedulingIgnoredDuringExecution": {"nodeSelectorTerms": [
            {"matchExpressions": [{"key": "zone", "operator": "In", "values": ["a"]}]}]}}}},
        "nodeAffinity"),
    "podAffinity": ({"affinity": {"podAffinity": {
        "requiredDuringSchedulingIgnoredDuringExecution": [TERM]}}},
        "podAffinity on kubernetes.io/hostname"),
    "spread_over_hostname": ({"topologySpreadConstraints": [
        {"maxSkew": 1, "topologyKey": "kubernetes.io/hostname",
         "whenUnsatisfiable": "DoNotSchedule",
         "labelSelector": {"matchLabels": {"color": "blue"}}}]},
        "topologySpread on kubernetes.io/hostname"),
    "anti_affinity_with_namespace_selector": ({"affinity": {"podAntiAffinity": {
        "requiredDuringSchedulingIgnoredDuringExecution": [dict(TERM, namespaceSelector={})]}}},
        "podAntiAffinity on kubernetes.io/hostname"),
}


@pytest.mark.parametrize("case", sorted(UNCLAIMED))
def test_a_hard_constraint_no_rule_claims_is_refused_by_name(case):
    spec, named = UNCLAIMED[case]
    dep = deployment("sched-perf-5000n")
    template = copy.deepcopy(dep.templates["measure"])
    template["spec"].update(spec)
    with pytest.raises(ManifestError, match=re.escape(named)):
        rules.require_claimed({"init": dep.templates["init"], "measure": template})


def test_what_is_soft_or_claimed_is_let_through():
    dep = deployment("sched-perf-5000n-antiaffinity")
    soft = copy.deepcopy(dep.templates["measure"])
    soft["spec"]["affinity"]["podAffinity"] = {
        "preferredDuringSchedulingIgnoredDuringExecution": [
            {"weight": 1, "podAffinityTerm": TERM}]}
    soft["spec"]["topologySpreadConstraints"] = [
        {"maxSkew": 1, "topologyKey": "kubernetes.io/hostname",
         "whenUnsatisfiable": "ScheduleAnyway"}]
    rules.require_claimed({"measure": soft})
    for config in ("sched-perf-5000n", "sched-perf-5000n-spread"):
        rules.require_claimed(deployment(config).templates)


def test_the_guard_is_the_set_ups_and_the_ledger_only_builds_the_list(monkeypatch):
    calls = []
    inner = rules.require_claimed
    monkeypatch.setattr(rules, "require_claimed", lambda t: (calls.append(1), inner(t)))
    dep = deployment("sched-perf-5000n-antiaffinity")
    reference.Ledger(dep.nodes(), dep.templates)
    assert not calls
    cell = {"name": "x", "config": "sched-perf-5000n-antiaffinity",
            "traffic": "closed256-live2000", "chips": 1}
    setup = harness.Setup(Manifest(), cell, True, "reference", None)
    setup.tear_down()
    assert calls == [1]


def test_the_run_stops_before_any_load(tmp_path, monkeypatch):
    m = Manifest()
    cell = {"name": "unchecked", "config": "sched-perf-5000n", "traffic": "closed256",
            "chips": 1}
    inner = Deployment.__init__

    def with_selector(self, config, toy=False):
        inner(self, config, toy=toy)
        self.templates["measure"] = copy.deepcopy(self.templates["measure"])
        self.templates["measure"]["spec"]["nodeSelector"] = {"disk": "ssd"}

    monkeypatch.setattr(Deployment, "__init__", with_selector)
    made = []
    monkeypatch.setattr(harness, "make_system", lambda *a, **kw: made.append(a))
    with pytest.raises(ManifestError, match="nodeSelector"):
        harness.run_cell(m, cell, 1, 1.0, False, True, system_name="reference")
    assert not made         # no system was built, let alone loaded
