"""Pods may complete.  The comparison's replay takes a deletion in at its
resourceVersion; the client records one beside the bind it learned; the closed
loop deletes the longest-bound pods once more than `live_pods` live, and
without that parameter deletes nothing and creates what it created before
(pinned from the parent commit, 963f05e)."""

import hashlib
import itertools
import json
import os
import random
import sys
import threading
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import compare  # noqa: E402
from perfbench.client import WatchClient  # noqa: E402
from perfbench.deployment import Deployment  # noqa: E402
from perfbench.manifest import Manifest  # noqa: E402
from perfbench.probes import Recorder  # noqa: E402

SEED = 2**31 + 77


def digest(x) -> str:
    return hashlib.sha256(json.dumps(x).encode()).hexdigest()[:16]


def deployment(config):
    return Deployment(Manifest().config(config), toy=True)


class Seen:
    """What the comparison reads of a client: binds `(key, node, rv)` and
    deletions `(key, rv)`."""

    def __init__(self, binds, deletions=()):
        self.bound = {key: (0.0, node, rv) for key, node, rv in binds}
        self.gone = {key: (0.0, rv) for key, rv in deletions}
        self.gone_pending = {}      # no pod of these runs is deleted unbound
        self.rebound, self.rv_regressions = [], 0


ASKED = object()     # the deletions the harness asked for: those the client saw, unless said


def verdict(config, binds, deletions=(), recovered=None, solves=None, role="measure",
            asked=ASKED):
    seen = Seen(binds, deletions)
    if asked is ASKED:
        asked = [k for k, _ in deletions] if deletions else None
    if recovered is None:
        recovered = {k: n for k, n, _ in binds if k not in (asked or ())}
    created = [(ns, name, role) for (ns, name), _, _ in binds]
    return compare.compare(deployment(config), created, seen, recovered,
                           solves if solves is not None else [[k for k, _, _ in binds]],
                           asked)


def failing(v):
    return {k for k, (value, lim) in v["checks"].items() if value > lim}


# -- the replay -------------------------------------------------------------------

def full_node(n=40):
    return [(("team-0", f"pod-{i}"), "node-3", 10 + i) for i in range(n)]


@pytest.mark.parametrize("delete_rv,breach", [(90, 0), (110, 1)])
def test_a_slot_is_free_from_its_deletes_rv_on_and_not_before(delete_rv, breach):
    binds = full_node() + [(("team-0", "late"), "node-3", 100)]
    v = verdict("sched-perf-5000n", binds, [(("team-0", "pod-0"), delete_rv)])
    assert v["checks"]["overcommitted_nodes"] == [breach, 0]
    assert v["correct"] is (not breach)


def test_a_node_deleted_from_and_refilled_is_not_summed_as_overcommitted():
    # 120 binds on one node over a run, never more than 40 alive: the parent's
    # sum at the end read this as overcommitted
    binds, deletions = [], []
    for i in range(120):
        binds.append((("team-0", f"pod-{i}"), "node-3", 10 + 2 * i))
        if i >= 40:
            deletions.append((("team-0", f"pod-{i - 40}"), 9 + 2 * i))
    solves = [[k for k, _, _ in binds[i:i + 10]] for i in range(0, 120, 10)]
    v = verdict("sched-perf-5000n", binds, deletions, solves=solves)
    assert v["correct"], v["checks"]


def test_anti_affinity_is_held_at_every_bind():
    a, b, c = ("sched-1", "a"), ("sched-1", "b"), ("sched-0", "c")
    ok = verdict("sched-perf-5000n-antiaffinity",
                 [(a, "node-1", 5), (b, "node-1", 9), (c, "node-2", 11)], [(a, 7)])
    assert ok["checks"]["colocated_pods"] == [0, 0] and ok["correct"]
    bad = verdict("sched-perf-5000n-antiaffinity",
                  [(a, "node-1", 5), (b, "node-1", 6), (c, "node-2", 11)], [(a, 7)])
    assert bad["checks"]["colocated_pods"] == [1, 0] and failing(bad) == {"colocated_pods"}
    assert list(ok["checks"])[-3:] == ["colocated_pods", "deletions_lost", "deletions_unasked"]


def test_a_deletion_replayed_before_its_own_bind_breaks_nothing():
    # the solves' record puts b's solve first; a (rv 5, gone at 7) is in a later one
    a, b = ("sched-1", "a"), ("sched-1", "b")
    v = verdict("sched-perf-5000n-antiaffinity", [(a, "node-1", 5), (b, "node-1", 9)],
                [(a, 7)], solves=[[b], [a]])
    assert v["correct"], v["checks"]


@pytest.mark.parametrize("solves", [
    [["b"], ["a"], ["c"]],      # the record puts the latest bind's solve first
    [["c"], ["b"], ["a"]],
    [["a", "b", "c"]],
    [],                         # no solve's record covers them
])
def test_a_rule_held_at_every_bind_is_replayed_in_the_stores_order(solves):
    # a on node-1 from rv 5 to 7, c on node-1 at rv 6, b elsewhere at rv 9:
    # whatever the order of the solves' record, a and c shared the node
    a, b, c = ("sched-1", "a"), ("sched-1", "b"), ("sched-1", "c")
    solves = [[("sched-1", n) for n in keys] for keys in solves]
    v = verdict("sched-perf-5000n-antiaffinity",
                [(a, "node-1", 5), (c, "node-1", 6), (b, "node-2", 9)], [(a, 7)], solves=solves)
    assert v["checks"]["colocated_pods"] == [1, 0] and failing(v) == {"colocated_pods"}
    # and with c after a's delete there is no breach, in any order of the record
    v = verdict("sched-perf-5000n-antiaffinity",
                [(a, "node-1", 5), (c, "node-1", 8), (b, "node-2", 9)], [(a, 7)], solves=solves)
    assert v["correct"], v["checks"]


def test_allocatable_is_held_in_the_stores_order_whatever_the_record_says():
    # the forty-first pod lands at rv 100, the slot is freed at rv 110; the
    # record lists a solve with a later bind first
    other = ("team-0", "elsewhere")
    binds = full_node() + [(("team-0", "late"), "node-3", 100), (other, "node-4", 120)]
    solves = [[other], [k for k, _, _ in binds[:-1]]]
    v = verdict("sched-perf-5000n", binds, [(("team-0", "pod-0"), 110)], solves=solves)
    assert v["checks"]["overcommitted_nodes"] == [1, 0]


def test_a_deletion_learned_before_its_pods_bind_frees_the_room_at_the_bind():
    # a relist may give a deletion the last rv read and the bind the list's:
    # the pod is replayed, and is gone for every bind after its own
    a, b = ("sched-1", "a"), ("sched-1", "b")
    v = verdict("sched-perf-5000n-antiaffinity", [(a, "node-1", 30), (b, "node-1", 31)],
                [(a, 4)])
    assert v["correct"], v["checks"]


# -- the deletions the harness asked for are its own truth -------------------------

def three_pods():
    a, b, c = ("team-0", "a"), ("team-0", "b"), ("team-0", "c")
    return a, b, c, [(a, "node-1", 1), (b, "node-2", 2), (c, "node-3", 3)]


def test_where_the_mix_deletes_two_more_numbers_are_held_and_nowhere_else():
    a, b, c, binds = three_pods()
    v = verdict("sched-perf-5000n", binds, [(a, 4)])
    assert list(v["checks"])[-2:] == ["deletions_lost", "deletions_unasked"]
    assert v["checks"]["deletions_lost"] == [0, 0] == v["checks"]["deletions_unasked"]
    assert v["correct"]
    # a mix that deletes and has not yet: both present, both 0
    v = verdict("sched-perf-5000n", binds, asked=[])
    assert v["checks"]["deletions_lost"] == [0, 0] == v["checks"]["deletions_unasked"]
    # a mix that never deletes: the parent's names and no other
    v = verdict("sched-perf-5000n", binds)
    assert "deletions_lost" not in v["checks"] and "deletions_unasked" not in v["checks"]


def test_an_acknowledged_deletion_lost_whole_is_counted():
    # asked and acknowledged; no event, no journal line, the pod lives on in
    # the store and the client alike: every other number reads as if it lived
    a, b, c, binds = three_pods()
    v = verdict("sched-perf-5000n", binds, asked=[a],
                recovered={a: "node-1", b: "node-2", c: "node-3"})
    assert v["checks"]["deletions_lost"] == [1, 0]
    assert failing(v) == {"deletions_lost", "journal_diff"}     # and it reads back


def test_an_acknowledged_deletion_the_client_never_saw_fails_by_its_own_number():
    # journaled, but no event ever reached the watch
    a, b, c, binds = three_pods()
    v = verdict("sched-perf-5000n", binds, asked=[a], recovered={b: "node-2", c: "node-3"})
    assert v["checks"]["deletions_lost"] == [1, 0] and failing(v) == {"deletions_lost"}


def test_a_deletion_nobody_asked_for_is_counted_and_its_pod_is_missed_in_the_journal():
    a, b, c, binds = three_pods()
    v = verdict("sched-perf-5000n", binds, [(a, 4), (b, 5)], asked=[a],
                recovered={c: "node-3"})
    assert v["checks"]["deletions_unasked"] == [1, 0]
    assert failing(v) == {"deletions_unasked", "journal_diff"}
    # in a cell whose mix deletes nothing it is the journal's number alone, as on the parent
    v = verdict("sched-perf-5000n", binds, [(b, 5)], asked=None,
                recovered={a: "node-1", c: "node-3"})
    assert failing(v) == {"journal_diff"} and "deletions_unasked" not in v["checks"]


def test_zone_skew_counts_what_lives_after_each_whole_solve():
    # six blue pods into zone 0 of one namespace, never more than five alive
    keys = [("team-0", f"p{i}") for i in range(6)]
    binds = [(k, "node-0", 10 + 10 * i) for i, k in enumerate(keys)]
    v = verdict("sched-perf-5000n-spread", binds, [(keys[0], 55)], solves=[keys[:5], keys[5:]])
    assert v["checks"]["max_zone_skew"] == [5, 5] and v["correct"]
    v = verdict("sched-perf-5000n-spread", binds, [(keys[0], 65)], solves=[keys[:5], keys[5:]])
    assert v["checks"]["max_zone_skew"] == [6, 5] and not v["correct"]


def test_journal_diff_is_exactly_the_pods_not_asked_deleted_each_where_the_client_saw_it():
    a, b, c = ("team-0", "a"), ("team-0", "b"), ("team-0", "c")
    binds = [(a, "node-1", 1), (b, "node-2", 2), (c, "node-3", 3)]
    gone = [(a, 4)]
    assert verdict("sched-perf-5000n", binds, gone)["checks"]["journal_diff"] == [0, 0]
    cases = {
        "a deleted pod reads back": {a: "node-1", b: "node-2", c: "node-3"},
        "a live pod does not": {b: "node-2"},
        "a live pod reads back elsewhere": {b: "node-2", c: "node-9"},
        "a pod nobody saw bound reads back bound": {b: "node-2", c: "node-3",
                                                     ("team-0", "x"): "node-4"},
    }
    for why, recovered in cases.items():
        v = verdict("sched-perf-5000n", binds, gone, recovered=recovered)
        assert v["checks"]["journal_diff"] == [1, 0] and failing(v) == {"journal_diff"}, why
    # created and never bound reads back unbound: no difference, as before
    v = verdict("sched-perf-5000n", binds, gone,
                recovered={b: "node-2", c: "node-3", ("team-0", "y"): ""})
    assert v["checks"]["journal_diff"] == [0, 0]


# -- the verdict the parent gave, on the same seeded binds -------------------------

PARENT_VERDICTS = {
    "sched-perf-5000n": {
        "unbound": [1, 0], "bound_twice": [0, 0], "stray_binds": [0, 0],
        "overcommitted_nodes": [12, 0], "journal_diff": [1, 0], "rv_regressions": [0, 0]},
    "sched-perf-5000n-spread": {
        "unbound": [1, 0], "bound_twice": [0, 0], "stray_binds": [0, 0],
        "overcommitted_nodes": [12, 0], "journal_diff": [1, 0], "rv_regressions": [0, 0],
        "max_zone_skew": [15, 5]},
}


@pytest.mark.parametrize("config", sorted(PARENT_VERDICTS))
def test_with_nothing_deleted_the_verdict_is_the_parents_number_for_number(config):
    dep = deployment(config)
    rng = random.Random(SEED)
    names = [n["metadata"]["name"] for n in dep.nodes()]
    walk = dep.namespace_walk(SEED, 1)
    binds = []
    for i in range(1500):
        # a lean on the first 16 nodes, so that some node is overcommitted
        node = names[rng.randrange(16)] if rng.random() < 0.45 \
            else names[rng.randrange(len(names))]
        binds.append(((next(walk), f"pod-{i}"), node, i + 1))
    created = [(ns, name, "init" if int(name[4:]) < 24 else "measure")
               for (ns, name), _, _ in binds]
    created.append(("team-0", "never-bound", "measure"))
    recovered = {k: n for k, n, _ in binds[1:]}
    keys = [k for k, _, _ in binds]
    solves = [keys[i:i + 100] for i in range(0, 1400, 100)]
    v = compare.compare(dep, created, Seen(binds), recovered, solves)
    assert v["checks"] == PARENT_VERDICTS[config] and v["correct"] is False
    assert list(v["checks"]) == list(PARENT_VERDICTS[config])      # and in the parent's order


# -- the client -------------------------------------------------------------------

class FakeWatch:
    def __init__(self, events, listed=None, list_rv=0):
        self.events, self.listed, self.list_rv = list(events), listed, list_rv
        self.expired = False

    def get(self, timeout):
        if self.events:
            return self.events.pop(0)
        if self.listed is not None and not self.expired:
            self.expired = True
        time.sleep(0.01)
        return None

    def relist(self):
        listed, self.listed, self.expired = self.listed, None, False
        return listed, self.list_rv

    def stop(self):
        pass


class FakeSource:
    def __init__(self, watch):
        self._watch = watch

    def watch(self):
        return self._watch


def run_client(watch, until):
    client = WatchClient(FakeSource(watch)).start()
    deadline = time.monotonic() + 10.0
    while not until(client) and time.monotonic() < deadline:
        time.sleep(0.01)
    client.stop()
    return client


def test_the_client_records_a_deletion_beside_the_bind_it_learned():
    events = [("ADDED", "ns", "a", "", 1), ("MODIFIED", "ns", "a", "node-1", 2),
              ("ADDED", "ns", "b", "", 3), ("MODIFIED", "ns", "b", "node-2", 4),
              ("DELETED", "ns", "a", "node-1", 5),
              # its bind was folded into the delete: a bind and a deletion all the same
              ("DELETED", "ns", "c", "node-3", 6),
              ("DELETED", "ns", "never-bound", "", 7)]
    c = run_client(FakeWatch(events), lambda c: c.events == len(events))
    assert c.bound[("ns", "a")][1:] == ("node-1", 2)        # as it was learned
    assert c.gone[("ns", "a")][1] == 5 and c.gone[("ns", "c")][1] == 6
    assert set(c.gone) == {("ns", "a"), ("ns", "c")}
    assert (c.n_bound(), c.n_live()) == (3, 1)
    assert not c.rebound and c.rv_regressions == 0


def test_a_relist_learns_a_deleted_pod_by_its_absence():
    events = [("MODIFIED", "ns", "a", "node-1", 2), ("MODIFIED", "ns", "b", "node-2", 4)]
    listed = {("ns", "b"): "node-2", ("ns", "d"): "node-4"}
    c = run_client(FakeWatch(events, listed, list_rv=30), lambda c: c.relists)
    assert set(c.gone) == {("ns", "a")}
    assert c.gone[("ns", "a")][1] == 4       # after the last event read: frees no room too early
    assert c.bound[("ns", "d")][1:] == ("node-4", 30)      # at or before the list's rv
    assert c.n_live() == 2 and c.expired == 1


# -- the closed loop --------------------------------------------------------------

class InstantSystem:
    """Binds every pod as it is created; records what it was asked."""

    def __init__(self):
        self.created, self.deletes = [], []
        self.bound, self.bind_log, self.gone = {}, [], {}
        self._mu = threading.Lock()

    # the client's side
    def n_bound(self):
        return len(self.bound)

    def n_live(self):
        return len(self.bound) - len(self.gone)

    def bind_log_since(self, i, n=None):
        with self._mu:
            return self.bind_log[i:] if n is None else self.bind_log[i:i + n]

    # the system's side
    def create(self, pod, role):
        m = pod["metadata"]
        with self._mu:
            key = (m["namespace"], m["name"])
            self.created.append(key)
            self.bound[key] = (0.0, "node-0", len(self.created))
            self.bind_log.append((0.0, len(self.created), key[0], key[1], "node-0"))

    def delete(self, namespace, name):
        with self._mu:
            self.deletes.append((namespace, name))
            self.gone[(namespace, name)] = (0.0, 0)


def drive(kind, mix, n, overrides=None, preload=0):
    m = Manifest()
    from perfbench import harness

    params = harness.traffic_params(m.traffic(mix), True, dict(overrides or {}, creators=1))
    system = InstantSystem()
    dep = deployment("sched-perf-5000n")
    for i in range(preload):
        system.create(dep.pod("init", f"init-{i}", "team-0"), "init")
    gen = m.generator(kind)(params, dep, system, system, Recorder(), SEED)
    gen.start()
    deadline = time.monotonic() + 20.0
    while len(gen.created) < n and time.monotonic() < deadline:
        time.sleep(0.005)
    gen.stop()
    return gen, system


# (namespace, name) of the first pods each mix creates for SEED, at 963f05e
PARENT_PODS = {"closed256": "37f72826c0802300", "steady": "37f72826c0802300"}


@pytest.mark.parametrize("kind,mix,overrides", [
    ("backlog", "closed256", None),
    ("open_loop", "steady", {"rate_pods_per_s": 2000.0}),
])
def test_the_cells_mixes_delete_nothing_and_create_what_the_parent_created(kind, mix, overrides):
    gen, system = drive(kind, mix, 160, overrides)
    assert len(gen.created) >= 160
    assert system.deletes == [] and not getattr(gen, "deleted", [])
    first = [list(c[:2]) for c in gen.created[:160]]
    assert digest(first) == PARENT_PODS[mix], first[:3]
    assert "live_pods" not in Manifest().traffic(mix)


def test_the_namespace_walks_are_the_parents():
    for config in ("sched-perf-5000n", "sched-perf-5000n-spread"):
        dep = deployment(config)
        walks = {s: list(itertools.islice(dep.namespace_walk(SEED, s), 40)) for s in (0, 1, 2)}
        assert digest(walks) == "22e2abebce18b86a"
        assert walks[1][:4] == ["team-1", "team-15", "team-14", "team-11"]
        assert dep.role_namespaces == {}


def test_with_live_pods_the_longest_bound_go_first_a_chunk_at_a_time():
    gen, system = drive("backlog", "closed256", 400, {"live_pods": 64, "topup_chunk": 16},
                        preload=70)
    assert len(system.deletes) >= 300 and len(system.deletes) % 16 == 0
    # set-up's pods first (the oldest), then the loop's own, in the order they were bound
    order = [(ns, name) for _, _, ns, name, _ in system.bind_log]
    assert system.deletes == order[:len(system.deletes)]
    assert [d[:2] for d in gen.deleted] == system.deletes
    # never handed out below the population, never held a chunk above it
    assert all(64 <= handed < 64 + 16 for _, handed, _ in gen.live[1:])
    assert len(set(system.deletes)) == len(system.deletes)


def test_a_configuration_names_its_namespaces_and_roles():
    dep = deployment("sched-perf-5000n-antiaffinity")
    assert dep.namespaces == ["sched-0", "sched-1"]
    assert dep.role_namespaces == {"init": ["sched-0"], "measure": ["sched-1"]}
    assert set(itertools.islice(dep.namespace_walk(SEED, 0, "init"), 8)) == {"sched-0"}
    assert set(itertools.islice(dep.namespace_walk(SEED, 1), 8)) == {"sched-1"}
    assert deployment("sched-perf-5000n").namespaces == [f"team-{i}" for i in range(16)]


# -- the same two faults planted under the served system's timed path ---------------

ANTI = {"name": "perf5k-antiaffinity-closed256-live2000",
        "config": "sched-perf-5000n-antiaffinity", "traffic": "closed256-live2000", "chips": 1}


def lose_deletions_whole(system):
    """Every fifth delete of a pod is acknowledged and does nothing."""
    store, n = system.store, itertools.count(1)
    inner = store.delete

    def delete(kind, name, namespace="default"):
        if kind == "Pod" and next(n) % 5 == 0:
            return None
        return inner(kind, name, namespace)

    store.delete = delete


def delete_unasked(system):
    """With every eighth delete the newest bound pod goes too, unasked."""
    from kubernetes_tpu.api import store as st

    store, n, newest = system.store, itertools.count(1), []
    inner_delete, inner_wave = store.delete, store.update_wave

    def update_wave(kind, updates, **kw):
        if kind == "Pod" and updates:
            newest[:] = [updates[-1][:2]]       # (name, namespace) of a pod being bound
        return inner_wave(kind, updates, **kw)

    def delete(kind, name, namespace="default"):
        if kind != "Pod":
            return inner_delete(kind, name, namespace)
        if next(n) % 8 == 0 and newest:
            try:
                inner_delete("Pod", *newest.pop())
            except (st.NotFound, IndexError):
                pass
        try:
            return inner_delete(kind, name, namespace)
        except st.NotFound:
            return None     # it went unasked, earlier; the ask is acknowledged all the same

    store.update_wave, store.delete = update_wave, delete


@pytest.mark.parametrize("plant,number", [
    (lose_deletions_whole, "deletions_lost"),
    (delete_unasked, "deletions_unasked"),
])
def test_a_deletion_fault_under_the_timed_path_comes_out_not_correct(plant, number):
    from perfbench import harness

    rec = harness.run_cell(
        Manifest(), ANTI, 2**31 + 36, 3.0, False, True, t_start=time.perf_counter(),
        plant=plant, overrides={"replay_walk": [8], "replay_cap_s": 2.0, "drain_s": 6.0},
    )
    v = rec["verdict"]
    assert len(rec["deleted"]) > 20
    assert not v["correct"]
    assert number in failing(v) and "journal_diff" in failing(v), v["checks"]
    # no rule of placement is broken by either: the pods that live are where they may be
    assert v["checks"]["colocated_pods"] == [0, 0] and v["checks"]["overcommitted_nodes"] == [0, 0]
