"""Preemption: tensorized dry-run kernel, victim-choice oracle parity,
and the end-to-end PostFilter path (evict through the store, nominate,
reschedule).

Reference semantics: framework/preemption/preemption.go:150-316,
plugins/defaultpreemption/default_preemption.go; policy divergences are
documented in ops/preemption.py and mirrored by testing/oracle.preempt.
"""

import time

import numpy as np
import pytest

from kubernetes_tpu.api import store as st
from kubernetes_tpu.api import types as api
from kubernetes_tpu.models.batch_scheduler import TPUBatchScheduler
from kubernetes_tpu.ops import preemption as pre_ops
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.scheduler.cache import SchedulerCache
from kubernetes_tpu.scheduler.metrics import Registry
from kubernetes_tpu.scheduler.preemption import PreemptionEvaluator
from kubernetes_tpu.testing.oracle import Oracle
from kubernetes_tpu.testing.wrappers import GI, MI, make_node, make_pod


# -- kernel ---------------------------------------------------------------


def test_dry_run_min_k():
    # one node, 3 victims, free 0; pod needs 2 cpu; victims free 1 cpu
    # each -> min_k = 2
    free = np.zeros((1, 2), np.float32)
    victim_req = np.array([[[1, 0], [1, 0], [1, 0]]], np.float32)
    valid = np.ones((1, 3), bool)
    pod_req = np.array([2, 0], np.float32)
    r = pre_ops.dry_run_victims(free, victim_req, valid, pod_req)
    assert bool(r.feasible[0])
    assert int(r.min_k[0]) == 2


def test_dry_run_infeasible_even_after_all_evictions():
    free = np.zeros((1, 1), np.float32)
    victim_req = np.full((1, 2, 1), 1.0, np.float32)
    valid = np.ones((1, 2), bool)
    pod_req = np.array([5.0], np.float32)
    r = pre_ops.dry_run_victims(free, victim_req, valid, pod_req)
    assert not bool(r.feasible[0])


def test_dry_run_padding_not_counted():
    # 1 real victim + 1 padding slot: k=2 must not become claimable
    free = np.zeros((1, 1), np.float32)
    victim_req = np.array([[[1.0], [99.0]]], np.float32)  # padding junk
    valid = np.array([[True, False]])
    pod_req = np.array([2.0], np.float32)
    r = pre_ops.dry_run_victims(free, victim_req, valid, pod_req)
    assert not bool(r.feasible[0])


# -- evaluator vs oracle ---------------------------------------------------


def _build_cluster(rng, n_nodes=6, n_victims=12):
    """Every node gets >= 2 victims (round-robin), so a 3500m preemptor
    on 4000m nodes never fits without eviction — preemption's actual
    precondition (PostFilter only runs after filters rejected all)."""
    nodes = [
        make_node(f"n{i}").capacity(cpu_milli=4000, mem=8 * GI, pods=20).obj()
        for i in range(n_nodes)
    ]
    bound = []
    for i in range(n_victims):
        node = f"n{i % n_nodes}"
        p = (
            make_pod(f"v{i}")
            .req(cpu_milli=int(rng.choice([500, 1000, 1500])), mem=GI)
            .priority(int(rng.integers(0, 5)))
            .node_name(node)
            .obj()
        )
        bound.append(p)
    return nodes, bound


def _evaluator_for(nodes, bound):
    tpu = TPUBatchScheduler()
    for n in nodes:
        tpu.add_node(n)
    for p in bound:
        tpu.assume(p, p.spec.node_name)
    cache = SchedulerCache(tpu.state)
    store = st.Store()
    ev = PreemptionEvaluator(tpu, cache, store)
    return ev


def test_victim_choice_oracle_parity(rng):
    """Randomized clusters: the evaluator's (node, victims) must equal the
    pure-Python policy mirror whenever the optimum is unique enough for
    both orderings to coincide (resource-only pods, unique priorities per
    node make it so)."""
    for trial in range(10):
        nodes, bound = _build_cluster(rng)
        preemptor = (
            make_pod("hi")
            .req(cpu_milli=3500, mem=GI)
            .priority(100)
            .obj()
        )
        ev = _evaluator_for(nodes, bound)
        with ev.cache.lock:
            plan = ev._plan(preemptor)
        oracle = Oracle(nodes, bound_pods=bound)
        want = oracle.preempt(preemptor)
        if plan is None:
            assert want is None, f"trial {trial}: oracle found {want}"
            continue
        assert want is not None, f"trial {trial}: oracle found nothing"
        node, victims = plan
        wnode, wvictims = want
        assert node == wnode, f"trial {trial}: {node} != {wnode}"
        assert sorted(v.meta.name for v in victims) == sorted(
            v.meta.name for v in wvictims
        ), trial


def test_never_policy_not_eligible():
    nodes = [make_node("n0").capacity(cpu_milli=1000).obj()]
    bound = [make_pod("v").req(cpu_milli=1000).priority(0).node_name("n0").obj()]
    ev = _evaluator_for(nodes, bound)
    pod = make_pod("hi").req(cpu_milli=1000).priority(10).obj()
    pod.spec.preemption_policy = "Never"
    assert not ev.eligible(pod)


def test_no_lower_priority_not_eligible():
    nodes = [make_node("n0").capacity(cpu_milli=1000).obj()]
    bound = [make_pod("v").req(cpu_milli=1000).priority(50).node_name("n0").obj()]
    ev = _evaluator_for(nodes, bound)
    pod = make_pod("lo").req(cpu_milli=1000).priority(10).obj()
    assert not ev.eligible(pod)


def test_verify_rejects_statically_blocked_candidate():
    """The pod is anti-affine to a label that survives eviction (carried
    by a HIGHER-priority pod), so resource-only candidates must be
    rejected by the re-solve verification."""
    nodes = [make_node("n0").capacity(cpu_milli=2000, pods=10).obj()]
    blocker = (
        make_pod("blocker")
        .req(cpu_milli=1000)
        .priority(200)  # not evictable
        .label("app", "x")
        .node_name("n0")
        .obj()
    )
    filler = (
        make_pod("filler").req(cpu_milli=1000).priority(0).node_name("n0").obj()
    )
    ev = _evaluator_for(nodes, [blocker, filler])
    pod = (
        make_pod("hi")
        .req(cpu_milli=500)
        .priority(100)
        .pod_anti_affinity({"app": "x"})
        .obj()
    )
    with ev.cache.lock:
        plan = ev._plan(pod)
    assert plan is None


def test_verify_accepts_when_eviction_clears_conflict():
    """Evicting the low-priority conflicting pod removes BOTH the resource
    shortage and the anti-affinity conflict."""
    nodes = [make_node("n0").capacity(cpu_milli=1000, pods=10).obj()]
    conflicter = (
        make_pod("conflicter")
        .req(cpu_milli=1000)
        .priority(0)
        .label("app", "x")
        .node_name("n0")
        .obj()
    )
    ev = _evaluator_for(nodes, [conflicter])
    pod = (
        make_pod("hi")
        .req(cpu_milli=500)
        .priority(100)
        .pod_anti_affinity({"app": "x"})
        .obj()
    )
    with ev.cache.lock:
        plan = ev._plan(pod)
    assert plan is not None
    node, victims = plan
    assert node == "n0"
    assert [v.meta.name for v in victims] == ["conflicter"]


# -- end-to-end through the scheduler --------------------------------------


def _mk_scheduler(store, **kw):
    s = Scheduler(store, **kw)
    s.informers.informer("Node").start()
    s.informers.informer("Pod").start()
    assert s.informers.wait_for_sync(10)
    return s


def test_preemption_end_to_end():
    """Full cluster; a high-priority pod arrives, evicts the cheapest
    victim set through the store, is nominated, and lands on the freed
    node on a later cycle.  preemption_* metrics populate."""
    store = st.Store()
    store.create(make_node("n0").capacity(cpu_milli=2000, pods=10).obj())
    store.create(make_node("n1").capacity(cpu_milli=2000, pods=10).obj())
    # fill both nodes with low-priority pods (bound directly via the API)
    for i, node in [(0, "n0"), (1, "n0"), (2, "n1"), (3, "n1")]:
        p = (
            make_pod(f"low-{i}")
            .req(cpu_milli=1000)
            .priority(i)  # low-0 is the cheapest victim
            .node_name(node)
            .obj()
        )
        p.status.phase = "Running"
        store.create(p)
    sched = _mk_scheduler(store)
    try:
        store.create(make_pod("hi").req(cpu_milli=1000).priority(100).obj())
        deadline = time.monotonic() + 15
        placed = None
        while time.monotonic() < deadline and not placed:
            sched.schedule_batch(timeout=0.2)
            placed = store.get("Pod", "hi").spec.node_name
        assert placed == "n0", placed
        # the cheapest victim (lowest priority, prio=0 on n0) was evicted
        with pytest.raises(KeyError):
            store.get("Pod", "low-0")
        # others survive
        for name in ("low-1", "low-2", "low-3"):
            store.get("Pod", name)
        assert sched.metrics.preemption_attempts.get("nominated") >= 1
        assert sched.metrics.preemption_victims.n >= 1
        # nomination was recorded through the API at some point
        assert placed == "n0"
    finally:
        sched.stop()


def test_preemption_not_triggered_when_feasible_elsewhere():
    store = st.Store()
    store.create(make_node("n0").capacity(cpu_milli=1000, pods=10).obj())
    store.create(make_node("n1").capacity(cpu_milli=2000, pods=10).obj())
    low = make_pod("low").req(cpu_milli=1000).priority(0).node_name("n0").obj()
    store.create(low)
    sched = _mk_scheduler(store)
    try:
        store.create(make_pod("hi").req(cpu_milli=1000).priority(100).obj())
        deadline = time.monotonic() + 10
        placed = None
        while time.monotonic() < deadline and not placed:
            sched.schedule_batch(timeout=0.2)
            placed = store.get("Pod", "hi").spec.node_name
        assert placed == "n1"
        store.get("Pod", "low")  # still alive
        assert sched.metrics.preemption_attempts.get("attempted") == 0
    finally:
        sched.stop()


def test_nominated_reservation_blocks_stealers():
    """A nominated pod's requests overlay its node for OTHER pods'
    snapshots (PodNominator analogue): the freed space cannot be stolen
    while the nominee waits to land."""
    tpu = TPUBatchScheduler()
    tpu.add_node(make_node("n0").capacity(cpu_milli=1000, pods=10).obj())
    nominee = make_pod("hi").req(cpu_milli=1000).priority(100).obj()
    stealer = make_pod("thief").req(cpu_milli=1000).priority(100).obj()
    # without the reservation the stealer fits
    assert tpu.schedule_pending([stealer]) == ["n0"]
    # with the nominee's reservation it must not
    assert tpu.schedule_pending(
        [stealer], reservations=[("n0", nominee)]
    ) == [None]
    # the nominee's own batch excludes its reservation and lands
    assert tpu.schedule_pending([nominee]) == ["n0"]


def test_nomination_lifecycle_in_cache():
    tpu = TPUBatchScheduler()
    tpu.add_node(make_node("n0").capacity(cpu_milli=2000, pods=10).obj())
    cache = SchedulerCache(tpu.state)
    pod = make_pod("p").req(cpu_milli=500).priority(5).obj()
    cache.nominate(pod, "n0")
    assert cache.nominations_excluding(set()) == [("n0", pod)]
    # the nominee's own batch is excluded
    from kubernetes_tpu.scheduler.queue import pod_key
    assert cache.nominations_excluding({pod_key(pod)}) == []
    # assuming the pod (it landed) spends the nomination
    cache.assume(pod, "n0")
    assert cache.nominations_excluding(set()) == []


def test_nominate_survives_conflict_and_notfound():
    """_nominate is best-effort: a concurrent writer between its get and
    update raises Conflict (a ValueError) — it must retry/drop, never
    propagate and kill the scheduling thread (advisor finding r3)."""
    from kubernetes_tpu.api import store as st
    from kubernetes_tpu.scheduler.preemption import PreemptionEvaluator

    store = st.Store()
    pod = make_pod("prey").obj()
    store.create(pod)

    class RacingStore:
        """First update hits a conflict (someone else wrote); the retry
        against the re-read object succeeds."""

        def __init__(self, inner):
            self.inner = inner
            self.calls = 0

        def get(self, *a, **k):
            return self.inner.get(*a, **k)

        def update(self, obj):
            self.calls += 1
            if self.calls == 1:
                raise st.Conflict("resourceVersion mismatch")
            return self.inner.update(obj)

    ev = object.__new__(PreemptionEvaluator)
    ev.store = RacingStore(store)
    ev._nominate(pod, "node-x")
    got = store.get("Pod", "prey", pod.meta.namespace)
    assert got.status.nominated_node_name == "node-x"

    # NotFound (pod deleted mid-flight) is silently dropped
    ev.store = store
    missing = make_pod("gone").obj()
    ev._nominate(missing, "node-y")  # must not raise


# -- PDBs (policy/v1 PodDisruptionBudget; preemption.go:290,463) ----------


def _pdb(name, selector, allowed, namespace="default"):
    pdb = api.PodDisruptionBudget(
        meta=api.ObjectMeta(name=name, namespace=namespace),
        spec=api.PodDisruptionBudgetSpec(
            selector=api.LabelSelector(match_labels=selector)
        ),
    )
    pdb.status.disruptions_allowed = allowed
    return pdb


def test_pdb_flags_partition_victims():
    from kubernetes_tpu.scheduler.preemption import PreemptionEvaluator

    pdbs = [_pdb("b", {"app": "db"}, 1)]
    victims = [
        make_pod(f"v{i}").labels(app="db").priority(i).obj() for i in range(3)
    ]
    flags = PreemptionEvaluator._pdb_flags(victims, pdbs)
    # budget allows ONE disruption: the first eviction tolerated, rest violate
    assert flags == [False, True, True]


def test_pdb_steers_victim_choice_end_to_end():
    """Two equivalent candidate nodes; the one whose victim violates a
    PDB must lose (minNumPDBViolatingScoreFunc is the FIRST criterion)."""
    store = st.Store()
    store.create(make_node("n0").capacity(cpu_milli=2000, pods=10).obj())
    store.create(make_node("n1").capacity(cpu_milli=2000, pods=10).obj())
    for name, node, app in (
        ("guarded", "n0", "db"),     # protected by a zero-budget PDB
        ("free", "n1", "web"),
    ):
        p = (
            make_pod(name).labels(app=app).req(cpu_milli=2000)
            .priority(1).node_name(node).obj()
        )
        p.status.phase = "Running"
        store.create(p)
    store.create(_pdb("db-pdb", {"app": "db"}, 0))
    sched = _mk_scheduler(store)
    try:
        store.create(make_pod("hi").req(cpu_milli=1500).priority(100).obj())
        deadline = time.monotonic() + 15
        placed = None
        while time.monotonic() < deadline and not placed:
            sched.schedule_batch(timeout=0.2)
            placed = store.get("Pod", "hi").spec.node_name
        assert placed == "n1", placed   # the unprotected victim's node
        store.get("Pod", "guarded")     # survives
        with pytest.raises(KeyError):
            store.get("Pod", "free")    # evicted
    finally:
        sched.stop()


# -- batched PostFilter (preempt_batch) vs the sequential loop -------------
#
# The batched path encodes the per-node victim tensors ONCE per pass and
# runs one [P, N, K] device dry-run; the wavefront-style conflict pass
# (touched-node recompute) must make its results IDENTICAL to running
# preempt() sequentially on the same failed-pod set — including gang
# preemptors and PDB-blocked candidates.


def _pod_result_key(res):
    if res is None:
        return None
    return (res.nominated_node, sorted(v.meta.name for v in res.victims))


def _store_evaluator(nodes, bound, preemptors, pdbs=()):
    """Evaluator with a REAL store behind it (preempt() re-fetches the
    preemptor and deletes victims through the API)."""
    tpu = TPUBatchScheduler()
    store = st.Store()
    for n in nodes:
        tpu.add_node(n)
        store.create(n)
    for p in bound:
        tpu.assume(p, p.spec.node_name)
        store.create(p)
    for p in preemptors:
        store.create(p)
    for pdb in pdbs:
        store.create(pdb)
    cache = SchedulerCache(tpu.state)
    ev = PreemptionEvaluator(tpu, cache, store, Registry())
    return ev


def _mixed_cluster(rng, n_nodes=6, n_victims=14, n_preemptors=4,
                   gang_of=0, db_every=0):
    nodes = [
        make_node(f"n{i}").capacity(cpu_milli=4000, mem=8 * GI, pods=20).obj()
        for i in range(n_nodes)
    ]
    bound = []
    for i in range(n_victims):
        pw = (
            make_pod(f"v{i}")
            .req(cpu_milli=int(rng.choice([500, 1000, 1500])), mem=GI)
            .priority(int(rng.integers(0, 5)))
            .node_name(f"n{i % n_nodes}")
        )
        if db_every and i % db_every == 0:
            pw = pw.labels(app="db")
        p = pw.obj()
        p.status.phase = "Running"
        bound.append(p)
    preemptors = []
    for j in range(n_preemptors):
        pw = make_pod(f"hi{j}").req(cpu_milli=3500, mem=GI).priority(
            int(rng.choice([50, 100, 200]))
        )
        if gang_of and j < gang_of:
            pw = pw.group("band", size=gang_of)
        preemptors.append(pw.obj())
    return nodes, bound, preemptors


def _assert_batch_matches_sequential(nodes, bound, preemptors, pdbs=()):
    ev_seq = _store_evaluator(nodes, bound, preemptors, pdbs)
    ev_bat = _store_evaluator(nodes, bound, preemptors, pdbs)
    seq = [
        ev_seq.preempt(p) if ev_seq.eligible(p) else None
        for p in preemptors
    ]
    bat = ev_bat.preempt_batch(preemptors)
    for j, (a, b) in enumerate(zip(seq, bat)):
        assert _pod_result_key(a) == _pod_result_key(b), (
            f"preemptor {j}: sequential {_pod_result_key(a)} != "
            f"batched {_pod_result_key(b)}"
        )
    # the surviving accounted state must be identical too
    assert sorted(ev_seq.tpu.state._pod_node.items()) == sorted(
        ev_bat.tpu.state._pod_node.items()
    )
    return ev_bat


def test_preempt_batch_matches_sequential(rng):
    """Randomized mixed-priority clusters: batched == sequential for the
    whole failed-pod set, INCLUDING passes where earlier preemptors'
    evictions touch later preemptors' candidate nodes (the conflict
    recompute)."""
    any_conflict = False
    for trial in range(8):
        nodes, bound, preemptors = _mixed_cluster(rng)
        ev = _assert_batch_matches_sequential(nodes, bound, preemptors)
        any_conflict = any_conflict or (
            ev.metrics.preemption_conflict_serializations.total > 0
        )
        assert ev.metrics.preemption_batch_size.n >= 1
    # with 4 preemptors over 6 nodes, at least one trial must have
    # exercised the touched-node recompute — otherwise the conflict
    # pass is untested
    assert any_conflict, "no trial exercised a cross-preemptor conflict"


def test_preempt_batch_gang_parity(rng):
    """Gang preemptors ride the shared pass: the multi-node accumulation
    (_plan_gang) consumes the batched candidates and stays identical to
    the sequential loop."""
    for trial in range(4):
        nodes, bound, preemptors = _mixed_cluster(
            rng, n_nodes=4, n_victims=8, n_preemptors=3, gang_of=2
        )
        _assert_batch_matches_sequential(nodes, bound, preemptors)


def test_preempt_batch_pdb_parity(rng):
    """PDB-blocked candidates: the per-level eviction reorder
    (non-violating victims first) and the device-side violation counts
    must rank identically to the sequential host-only pass."""
    for trial in range(4):
        nodes, bound, preemptors = _mixed_cluster(rng, db_every=2)
        pdbs = [_pdb("db-pdb", {"app": "db"}, 1)]
        ev = _assert_batch_matches_sequential(
            nodes, bound, preemptors, pdbs
        )
        assert ev.pdb_aware


def test_preempt_batch_pdb_blocked_metric():
    """A candidate whose only victim violates a zero-budget PDB ranks
    last and counts into preemption_pdb_blocked_total."""
    nodes = [
        make_node(f"n{i}").capacity(cpu_milli=2000, pods=10).obj()
        for i in range(2)
    ]
    bound = []
    for name, node, app in (("guarded", "n0", "db"), ("free", "n1", "web")):
        p = (
            make_pod(name).labels(app=app).req(cpu_milli=2000)
            .priority(1).node_name(node).obj()
        )
        p.status.phase = "Running"
        bound.append(p)
    preemptor = make_pod("hi").req(cpu_milli=1500).priority(100).obj()
    ev = _store_evaluator(
        nodes, bound, [preemptor], [_pdb("db-pdb", {"app": "db"}, 0)]
    )
    results = ev.preempt_batch([preemptor])
    assert results[0] is not None
    assert results[0].nominated_node == "n1"  # the unprotected node wins
    assert ev.metrics.preemption_pdb_blocked_total.total >= 1


def test_preempt_batch_oracle_parity(rng):
    """Randomized snapshots: the batched plan for a single preemptor
    must equal the pure-Python policy mirror (the documented
    reprieve-policy divergence stays pinned — Oracle.preempt implements
    OUR minimal-prefix policy, not the reference's reprieve pass)."""
    for trial in range(8):
        nodes, bound = _build_cluster(rng)
        preemptor = (
            make_pod("hi").req(cpu_milli=3500, mem=GI).priority(100).obj()
        )
        ev = _store_evaluator(nodes, bound, [preemptor])
        with ev.shared_pass([preemptor]):
            assert not ev._shared.fallback
            plan = ev._plan(preemptor)
        want = Oracle(nodes, bound_pods=bound).preempt(preemptor)
        if plan is None:
            assert want is None, f"trial {trial}: oracle found {want}"
            continue
        assert want is not None, f"trial {trial}: oracle found nothing"
        node, victims = plan
        wnode, wvictims = want
        assert node == wnode, trial
        assert sorted(v.meta.name for v in victims) == sorted(
            v.meta.name for v in wvictims
        ), trial


def test_preempt_batch_fallback_parity(rng):
    """Injected batched-dispatch failures (the breaker wire): the pass
    falls back to the per-pod exact-parity path and still produces the
    sequential loop's results; the shared solve breaker trips."""
    from kubernetes_tpu.testing import faults

    nodes, bound, preemptors = _mixed_cluster(rng)
    ev_seq = _store_evaluator(nodes, bound, preemptors)
    seq = [
        ev_seq.preempt(p) if ev_seq.eligible(p) else None
        for p in preemptors
    ]
    ev_bat = _store_evaluator(nodes, bound, preemptors)
    reg = faults.FaultRegistry(seed=1)
    reg.fail("batch.preemption", n=2)  # first attempt AND its retry
    with faults.armed(reg):
        bat = ev_bat.preempt_batch(preemptors)
    assert reg.fired.get("batch.preemption") == 2
    assert ev_bat.tpu.breaker.state == ev_bat.tpu.breaker.OPEN
    for a, b in zip(seq, bat):
        assert _pod_result_key(a) == _pod_result_key(b)


def test_preempt_batch_corrupt_result_falls_back(rng):
    """NaN-grade corruption of the batched dry-run result trips the
    health check (out-of-range victim counts) on BOTH attempts; the
    pass degrades to the per-pod path with parity."""
    from kubernetes_tpu.testing import faults

    nodes, bound, preemptors = _mixed_cluster(rng)
    ev_seq = _store_evaluator(nodes, bound, preemptors)
    seq = [
        ev_seq.preempt(p) if ev_seq.eligible(p) else None
        for p in preemptors
    ]
    ev_bat = _store_evaluator(nodes, bound, preemptors)
    reg = faults.FaultRegistry(seed=2)
    reg.corrupt("batch.preemption", n=2)
    with faults.armed(reg):
        bat = ev_bat.preempt_batch(preemptors)
    for a, b in zip(seq, bat):
        assert _pod_result_key(a) == _pod_result_key(b)


def test_eligible_uses_shared_min_priority():
    """The satellite: eligibility inside a shared pass consults the
    pass's cached min-existing-priority instead of scanning
    state._pods per failed pod."""
    nodes = [make_node("n0").capacity(cpu_milli=2000, pods=10).obj()]
    victim = (
        make_pod("v").req(cpu_milli=2000).priority(5).node_name("n0").obj()
    )
    victim.status.phase = "Running"
    hi = make_pod("hi").req(cpu_milli=500).priority(100).obj()
    lo = make_pod("lo").req(cpu_milli=500).priority(3).obj()
    ev = _store_evaluator(nodes, [victim], [hi, lo])
    assert ev.min_existing_priority() == 5
    with ev.shared_pass([hi, lo]) as ctx:
        assert ctx.min_prio == 5
        assert ev.eligible(hi)        # 100 > 5
        assert not ev.eligible(lo)    # 3 < 5: nothing evictable
        # the cached value is consulted — mutating state mid-pass must
        # not change eligibility answers (one scan per pass)
        ev.tpu.state.remove_pod(victim)
        assert ev.eligible(hi)
    # outside the pass the live scan is back
    assert ev.min_existing_priority() is None
    assert not ev.eligible(hi)


def test_scheduler_postfilter_uses_batched_pass():
    """End-to-end: the scheduler's PostFilter stage routes the failed
    batch through one shared preemption pass (preemption_batch_size
    observes) and the nominee lands."""
    store = st.Store()
    store.create(make_node("n0").capacity(cpu_milli=2000, pods=10).obj())
    for i in range(2):
        p = (
            make_pod(f"low-{i}").req(cpu_milli=1000).priority(i)
            .node_name("n0").obj()
        )
        p.status.phase = "Running"
        store.create(p)
    sched = _mk_scheduler(store)
    try:
        store.create(make_pod("hi").req(cpu_milli=1500).priority(100).obj())
        deadline = time.monotonic() + 15
        placed = None
        while time.monotonic() < deadline and not placed:
            sched.schedule_batch(timeout=0.2)
            placed = store.get("Pod", "hi").spec.node_name
        assert placed == "n0"
        assert sched.metrics.preemption_batch_size.n >= 1
        assert sched.metrics.preemption_solve_duration.n >= 1
    finally:
        sched.stop()


def test_overload_level1_caps_instead_of_deferring():
    """The degradation ladder's level-1 action is now a CAP on the
    preemption batch (the batched solve amortized the per-pod cost),
    not a full deferral; level 2 still defers."""
    store = st.Store()
    store.create(make_node("n0").capacity(cpu_milli=2000, pods=10).obj())
    p = make_pod("low").req(cpu_milli=2000).priority(0).node_name("n0").obj()
    p.status.phase = "Running"
    store.create(p)
    sched = _mk_scheduler(store)
    try:
        # push the controller to level 1 (ewma > slo)
        for _ in range(10):
            sched.overload.note_cycle(2 * sched.overload.slo * 0.9)
        assert sched.overload.level() == 1
        store.create(make_pod("hi").req(cpu_milli=1500).priority(100).obj())
        deadline = time.monotonic() + 15
        placed = None
        while time.monotonic() < deadline and not placed:
            sched.schedule_batch(timeout=0.2)
            placed = store.get("Pod", "hi").spec.node_name
        # level 1 must NOT have deferred the preemption outright
        assert placed == "n0"
        assert sched.metrics.preemption_attempts.get("nominated") >= 1
    finally:
        sched.stop()


def test_shed_preemptor_retries_when_the_cluster_goes_idle():
    """Level 2 defers the PostFilter pass, and the ladder's average only
    moves when a cycle runs.  A preemptor shed at level 2 with nothing
    else going on used to stay parked behind a level no cycle would ever
    lower, until the 300 s unschedulable flush (on the chip: 8
    preemptors behind a 3,900-pod burst).  Shed pods retry with backoff,
    and those retries are the cycles that bring the level down."""
    from kubernetes_tpu.scheduler.config import SchedulerConfiguration

    store = st.Store()
    store.create(make_node("n0").capacity(cpu_milli=2000, pods=10).obj())
    p = make_pod("low").req(cpu_milli=2000).priority(0).node_name("n0").obj()
    p.status.phase = "Running"
    store.create(p)
    sched = _mk_scheduler(
        store,
        config=SchedulerConfiguration(pod_initial_backoff_seconds=0.1),
    )
    try:
        # a burst's worth of slow cycles: just past the level-2 threshold
        for _ in range(10):
            sched.overload.note_cycle(2.2 * sched.overload.slo)
        assert sched.overload.level() == 2
        store.create(make_pod("hi").req(cpu_milli=1500).priority(100).obj())
        deadline = time.monotonic() + 15
        placed = None
        while time.monotonic() < deadline and not placed:
            sched.schedule_batch(timeout=0.2)
            placed = store.get("Pod", "hi").spec.node_name
        assert sched.metrics.overload_shed_total.total >= 1  # it WAS shed
        assert placed == "n0"
        assert sched.overload.level() < 2
    finally:
        sched.stop()


def test_cold_compile_cycle_is_not_overload(monkeypatch):
    """A first-of-a-bucket cycle blocks for seconds in trace + compile.
    That wall is not load: fed to the ladder it read as severe overload
    (level 2 defers PostFilter), and in an idle cluster no later cycle
    brought the level down — on the chip, preemptors sent right after a
    cold start waited for the 300 s unschedulable flush.  A cycle that
    compiled (utils/compileclock) is not fed to the ladder."""
    import jax.monitoring

    from kubernetes_tpu.scheduler.config import SchedulerConfiguration

    store = st.Store()
    store.create(make_node("n0").capacity(cpu_milli=2000, pods=10).obj())
    store.create(make_node("n1").capacity(cpu_milli=500, pods=10).obj())
    p = make_pod("low").req(cpu_milli=2000).priority(0).node_name("n0").obj()
    p.status.phase = "Running"
    store.create(p)
    sched = _mk_scheduler(
        store, config=SchedulerConfiguration(batch_latency_slo_seconds=0.1)
    )
    inner = sched.tpu.schedule_pending_async
    walls = []

    def first_of_a_bucket(pods, **kw):
        # what a jit call that has to compile does to its caller: block,
        # then report the step on the caller's thread (as JAX does)
        if not walls:
            t0 = time.time()
            time.sleep(0.8)  # 8x the SLO: level 2 if it counted as load
            walls.append(time.time() - t0)
            jax.monitoring.record_event_duration_secs(
                "/jax/core/compile/backend_compile_duration", walls[0]
            )
        return inner(pods, **kw)

    monkeypatch.setattr(sched.tpu, "schedule_pending_async", first_of_a_bucket)

    def drive(name):
        deadline = time.monotonic() + 15
        placed = None
        while time.monotonic() < deadline and not placed:
            sched.schedule_batch(timeout=0.2)
            placed = store.get("Pod", name).spec.node_name
        return placed

    try:
        # the cold cycle: one small pod, bound while its caller "compiled"
        store.create(make_pod("first").req(cpu_milli=100).obj())
        assert drive("first") == "n1" and walls
        assert sched.overload.level() == 0
        # a preemptor right behind it, nothing else going on: its
        # PostFilter pass runs now, not after the unschedulable flush
        store.create(make_pod("hi").req(cpu_milli=1500).priority(100).obj())
        assert drive("hi") == "n0"
        assert sched.metrics.overload_shed_total.total == 0
    finally:
        sched.stop()


def test_gang_preemption_evicts_across_nodes():
    """A whole gang preempts: victims accumulate over multiple nodes
    until the group fits all-or-nothing (previously gang members were
    preemption-ineligible)."""
    store = st.Store()
    for i in range(2):
        store.create(make_node(f"n{i}").capacity(cpu_milli=2000, pods=10).obj())
    for i in range(2):
        p = (
            make_pod(f"low-{i}").req(cpu_milli=2000).priority(0)
            .node_name(f"n{i}").obj()
        )
        p.status.phase = "Running"
        store.create(p)
    sched = _mk_scheduler(store)
    try:
        # gang of 2, each needing a whole node: must evict BOTH low pods
        for i in range(2):
            store.create(
                make_pod(f"g{i}").req(cpu_milli=2000).priority(100)
                .group("band", size=2).obj()
            )
        deadline = time.monotonic() + 20
        placed = []
        while time.monotonic() < deadline and len(placed) < 2:
            sched.schedule_batch(timeout=0.2)
            placed = [
                store.get("Pod", f"g{i}").spec.node_name
                for i in range(2)
                if store.get("Pod", f"g{i}").spec.node_name
            ]
        assert sorted(placed) == ["n0", "n1"], placed
        for i in range(2):
            with pytest.raises(KeyError):
                store.get("Pod", f"low-{i}")
    finally:
        sched.stop()
