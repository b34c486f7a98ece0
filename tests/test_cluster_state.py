"""Incremental ClusterState: parity with bulk builds, node/pod lifecycle,
and the assume/forget protocol (cache.go:57-260 analogue)."""

import copy
import gc
import sys
import time

import numpy as np
import pytest

from kubernetes_tpu.api import types as api
from kubernetes_tpu.models.batch_scheduler import TPUBatchScheduler
from kubernetes_tpu.ops import assign, schema
from kubernetes_tpu.scheduler.cache import SchedulerCache
from kubernetes_tpu.testing.wrappers import GI, MI, make_node, make_pod
from kubernetes_tpu.utils import trace


def _nodes(n=8):
    return [
        make_node(f"n{i}")
        .capacity(cpu_milli=8000, mem=16 * GI, pods=10)
        .zone(f"z{i % 3}")
        .obj()
        for i in range(n)
    ]


def _pods(p=12):
    return [
        make_pod(f"p{i}").req(cpu_milli=1000, mem=GI).obj() for i in range(p)
    ]


def test_state_matches_bulk_build():
    nodes, pods = _nodes(), _pods()
    bound = [make_pod("b0").req(cpu_milli=2000).node_name("n3").obj()]

    b1 = schema.SnapshotBuilder()
    snap1, meta1 = b1.build(nodes, pods, bound_pods=bound)

    b2 = schema.SnapshotBuilder()
    st = schema.ClusterState(b2)
    for nd in nodes:
        st.add_node(nd)
    st.add_pod(bound[0])
    snap2, meta2 = b2.build_from_state(st, pods)

    for a1, a2 in zip(snap1.cluster, snap2.cluster):
        np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    r1 = np.asarray(assign.greedy_assign(snap1).assignment)
    r2 = np.asarray(assign.greedy_assign(snap2).assignment)
    np.testing.assert_array_equal(r1, r2)
    assert meta2.node_name(0) == "n0"


def test_assume_forget_roundtrip():
    st = schema.ClusterState(schema.SnapshotBuilder())
    for nd in _nodes():
        st.add_node(nd)
    before = [a.copy() for a in st.tensors()]
    pod = make_pod("x").req(cpu_milli=1500, mem=2 * GI).host_port(8080).obj()
    st.add_pod(pod, "n2")
    assert st.has_pod(pod)
    changed = st.tensors()
    assert changed.requested[2, schema.RESOURCE_CPU] == 1500
    assert changed.port_bits[2].any()
    st.remove_pod(pod)
    after = st.tensors()
    for b, a in zip(before, after):
        np.testing.assert_array_equal(np.asarray(b), np.asarray(a))


def test_update_node_preserves_usage():
    st = schema.ClusterState(schema.SnapshotBuilder())
    nodes = _nodes()
    for nd in nodes:
        st.add_node(nd)
    st.add_pod(make_pod("x").req(cpu_milli=1000).obj(), "n1")
    updated = (
        make_node("n1")
        .capacity(cpu_milli=16000, mem=32 * GI, pods=20)
        .zone("z9")
        .label("disk", "ssd")
        .obj()
    )
    st.update_node(updated)
    t = st.tensors()
    assert t.allocatable[1, schema.RESOURCE_CPU] == 16000
    assert t.requested[1, schema.RESOURCE_CPU] == 1000  # preserved
    assert t.label_bits[1].any()


def test_remove_node_frees_row_for_reuse():
    st = schema.ClusterState(schema.SnapshotBuilder())
    for nd in _nodes(4):
        st.add_node(nd)
    st.remove_node("n1")
    t = st.tensors()
    assert not t.node_valid[1]
    assert st.num_nodes == 3
    st.add_node(make_node("n9").capacity(cpu_milli=4000, mem=GI).obj())
    t = st.tensors()
    assert t.node_valid[1]  # freed row reused
    assert st.node_names[1] == "n9"


def test_scheduler_incremental_flow():
    """schedule_pending + assume: the second batch sees the first batch's
    placements; forget releases them."""
    sched = TPUBatchScheduler()
    for nd in _nodes(2):
        sched.add_node(nd)
    # Each node fits 8 such pods on cpu (8000/1000).
    first = [make_pod(f"a{i}").req(cpu_milli=1000).obj() for i in range(16)]
    names = sched.schedule_pending(first)
    assert all(n is not None for n in names)
    for p, n in zip(first, names):
        sched.assume(p, n)
    # cluster is now cpu-full: nothing fits
    second = [make_pod("b0").req(cpu_milli=1000).obj()]
    assert sched.schedule_pending(second) == [None]
    # forget one, retry: fits again
    sched.forget(first[0])
    assert sched.schedule_pending(second)[0] is not None


def test_growth_past_initial_capacity():
    st = schema.ClusterState(schema.SnapshotBuilder())
    nodes = _nodes(70)  # > min_nodes default, forces several grows
    for nd in nodes:
        st.add_node(nd)
    t = st.tensors()
    assert st.num_nodes == 70
    assert t.node_valid[:70].all()
    assert t.allocatable.shape[0] >= 70
    # scalar resource widening
    st.add_pod(
        make_pod("gpu").req(cpu_milli=100, **{"example.com/gpu": 2}).obj(), "n0"
    )
    t = st.tensors()
    gi = st.builder.resource_names.index("example.com/gpu")
    assert t.requested[0, gi] == 2


# -- the bound-pod constraint index (ClusterState.bound) ---------------------
#
# After any sequence of mutations, every leaf of the spread, term and
# preferred-term tables that build_from_state reads from the index must
# equal, byte for byte, what SnapshotBuilder.build() derives from the
# same pod objects in one pass.

_NODE_AXIS_LEAVES = {
    ("spread", "node_matches"), ("terms", "node_matches"),
    ("terms", "node_owners"), ("prefpod", "node_counts"),
    ("prefpod", "owner_weight"),
}
_APPS = ("web", "db", "cache")


def _index_nodes(n):
    return [
        make_node(f"n{i}")
        .capacity(cpu_milli=64000, mem=64 * GI, pods=110)
        .zone(f"z{i % 3}")
        .obj()
        for i in range(n)
    ]


def _template_pod(rng, name, kind=None):
    """A pod of one of the templates the tables read: plain,
    hard-spread, required anti-affinity, required affinity, preferred
    (anti-)affinity.  A few carry a label of their own, as a
    pod-template-hash does."""
    kind = kind or rng.choice(["plain", "plain", "spread", "anti", "aff", "pref"])
    app = str(rng.choice(_APPS))
    ns = str(rng.choice(["default", "team-a"]))
    w = make_pod(name, namespace=ns).req(cpu_milli=100, mem=64 * MI).label("app", app)
    if rng.random() < 0.2:
        w = w.label("hash", f"h{int(rng.integers(0, 1000))}")
    other = str(rng.choice(_APPS))
    if kind == "spread":
        w = w.spread(topology_key=api.LABEL_ZONE, selector={"app": app})
    elif kind == "anti":
        w = w.pod_anti_affinity({"app": other}, topology_key=api.LABEL_HOSTNAME)
    elif kind == "aff":
        w = w.pod_affinity({"app": other}, topology_key=api.LABEL_ZONE)
    elif kind == "pref":
        aff = w._affinity()
        term = api.PodAffinityTerm(
            label_selector=api.LabelSelector(match_labels={"app": other}),
            topology_key=api.LABEL_ZONE,
        )
        weighted = api.WeightedPodAffinityTerm(weight=int(rng.integers(1, 100)), term=term)
        if rng.random() < 0.5:
            aff.pod_affinity = api.PodAffinity(preferred=[weighted])
        else:
            aff.pod_anti_affinity = api.PodAntiAffinity(preferred=[weighted])
    return w.obj()


def _on_node(pod, node_name):
    """The pod as build() wants a bound pod: its node in its spec."""
    q = copy.copy(pod)
    q.spec = copy.copy(pod.spec)
    q.spec.node_name = node_name
    return q


def _assert_index_coherent(st):
    b = st.bound
    assert len(b) == len(st._pods) == len(b._slots) == len(b._keys)
    for key, pod in st._pods.items():
        slot = b._slots[key]
        assert b._keys[slot] == key
        assert b.node[slot] == st._rows[st._pod_node[key]], key
        assert b.signatures[b.sig[slot]].key == schema._label_signature(pod)
    distinct = {schema._label_signature(p) for p in st._pods.values()}
    assert b.live_signatures == len(distinct)
    assert sum(s.refs for s in b.signatures if s is not None) == len(b)
    assert list(b._owners) == [k for k, p in st._pods.items() if schema._has_pod_terms(p)]


def _assert_tables_match_bulk(st, pending):
    """build_from_state against build() on the same objects: every leaf
    of spread, terms and prefpod, byte for byte (node-axis leaves column
    by column through each side's own row of the node; what is left of
    the state's wider axis must be zero)."""
    _assert_index_coherent(st)
    nodes = [st._node_objs[name] for name in st._rows]
    bound = [_on_node(p, st._pod_node[k]) for k, p in st._pods.items()]
    want, _ = schema.SnapshotBuilder(st.builder.limits).build(nodes, pending, bound_pods=bound)
    got, _ = st.builder.build_from_state(st, pending)
    cols = np.array([st._rows[nd.meta.name] for nd in nodes], dtype=np.int64)
    for part in ("spread", "terms", "prefpod"):
        ta, tb = getattr(got, part), getattr(want, part)
        for leaf in type(ta)._fields:
            a, b = np.asarray(getattr(ta, leaf)), np.asarray(getattr(tb, leaf))
            if (part, leaf) in _NODE_AXIS_LEAVES:
                rest = np.ones(a.shape[1], dtype=bool)
                rest[cols] = False
                assert not a[:, rest].any(), f"{part}.{leaf}: counts on a free row"
                a, b = a[:, cols], b[:, : len(nodes)]
            assert a.dtype == b.dtype and a.shape == b.shape, f"{part}.{leaf}"
            assert np.ascontiguousarray(a).tobytes() == np.ascontiguousarray(b).tobytes(), \
                f"{part}.{leaf} differs"
    return got


def _filled_cache(rng, n_nodes=24, n_pods=120):
    st = schema.ClusterState(schema.SnapshotBuilder())
    cache = SchedulerCache(st)
    for nd in _index_nodes(n_nodes):
        cache.add_node(nd)
    for i in range(n_pods):
        cache.add_pod(_on_node(_template_pod(rng, f"b{i}"), f"n{int(rng.integers(0, n_nodes))}"))
    return cache, st


def _batches(rng):
    """A plain batch, and one with a row of every kind."""
    plain = [_template_pod(rng, f"q{i}", "plain") for i in range(6)]
    mixed = [_template_pod(rng, f"m{i}", k)
             for i, k in enumerate(["plain", "spread", "anti", "aff", "pref", "spread", "anti"])]
    return plain, mixed


def _mutate(kind, rng, cache, st, step):
    """One mutation of `kind` through the scheduler cache, as the
    informer and the solve path drive it."""
    live = list(st._pods)
    nodes = list(st._rows)
    node = str(rng.choice(nodes))
    if kind == "add_pod":
        cache.add_pod(_on_node(_template_pod(rng, f"a{step}"), node))
    elif kind == "remove_pod" and live:
        key = str(rng.choice(live))
        # the informer's object, not the one accounted: a bare pod of that key
        ns, _, name = key.partition("/")
        cache.remove_pod(_on_node(make_pod(name, namespace=ns).req(
            cpu_milli=100, mem=64 * MI).obj(), st._pod_node[key]))
    elif kind == "update_pod_labels" and live:
        key = str(rng.choice(live))
        old = st._pods[key]
        new = copy.copy(old)
        new.meta = copy.copy(old.meta)
        new.meta.labels = dict(old.meta.labels, app=str(rng.choice(_APPS)), rev=f"r{step}")
        new.spec = copy.copy(old.spec)
        new.spec.node_name = st._pod_node[key]
        cache.update_pod(_on_node(old, st._pod_node[key]), new)
    elif kind == "assume_confirm_other_object":
        pod = _template_pod(rng, f"c{step}")
        cache.assume(pod, node)
        # the informer delivers another object of the same key, with
        # labels the assumed one does not have: the assumed object stays
        other = _on_node(pod, node)
        other.meta = copy.copy(pod.meta)
        other.meta.labels = dict(pod.meta.labels, delivered="yes")
        cache.add_pod(other)
        assert st._pods[f"{pod.meta.namespace}/{pod.meta.name}"] is pod
    elif kind == "assume_forget":
        pod = _template_pod(rng, f"f{step}")
        cache.assume(pod, node)
        if rng.random() < 0.7:
            assert cache.forget(pod)
    elif kind == "remove_node" and len(nodes) > 4:
        cache.remove_node(node)
    elif kind == "compaction":
        # drain from the front until the survivors are moved into the
        # holes, then let new nodes take the rows that came free
        moved = st.compaction_moved_rows_total
        for name in nodes:
            if len(st._rows) <= 3 or st.compaction_moved_rows_total > moved:
                break
            cache.remove_node(name)
        for nd in _index_nodes(10):
            nd.meta.name = f"r{step}-{nd.meta.name}"
            cache.add_node(nd)


_MUTATIONS = ["add_pod", "remove_pod", "update_pod_labels", "assume_confirm_other_object",
              "assume_forget", "remove_node", "compaction"]


@pytest.mark.parametrize("kind", _MUTATIONS + ["all_kinds"])
def test_constraint_tables_from_the_index_equal_the_bulk_build(kind):
    rng = np.random.default_rng(26 + len(kind))
    st = schema.ClusterState(schema.SnapshotBuilder(schema.SnapshotLimits(min_nodes=8)))
    cache = SchedulerCache(st)
    for nd in _index_nodes(40):
        cache.add_node(nd)
    for i in range(150):
        cache.add_pod(_on_node(_template_pod(rng, f"b{i}"), f"n{int(rng.integers(0, 40))}"))
    plain, mixed = _batches(rng)
    _assert_tables_match_bulk(st, mixed)
    kinds = _MUTATIONS if kind == "all_kinds" else [kind]
    steps = 1 if kind == "compaction" else 40
    for step in range(steps):
        _mutate(str(rng.choice(kinds)), rng, cache, st, step)
        if step % 8 == 7 or step == steps - 1:
            _assert_tables_match_bulk(st, mixed)
    if kind == "compaction":
        assert st.compaction_moved_rows_total > 0, "the drain moved no row"
    _assert_tables_match_bulk(st, plain)
    got = _assert_tables_match_bulk(st, mixed)
    # the comparison is not of empty tables
    assert got.spread.node_matches.any() and got.terms.node_matches.any()
    assert got.terms.node_owners.any() and got.prefpod.owner_weight.any()


def test_a_bound_pod_with_an_unsupported_term_is_still_skipped():
    rng = np.random.default_rng(7)
    cache, st = _filled_cache(rng)
    _, mixed = _batches(rng)
    before, _ = st.builder.build_from_state(st, mixed)
    bad = make_pod("bad").req(cpu_milli=100).label("app", "web").pod_anti_affinity(
        {"app": "zzz"}).pod_affinity({"app": "zzz"}).obj()
    for aff in (bad.spec.affinity.pod_anti_affinity, bad.spec.affinity.pod_affinity):
        aff.required[0].namespace_selector = api.LabelSelector(match_labels={"team": "x"})
    cache.add_pod(_on_node(bad, "n0"))
    after = _assert_tables_match_bulk(st, mixed)      # does not raise
    # its terms made no row: only the pod itself is counted, as a match
    np.testing.assert_array_equal(after.terms.valid, before.terms.valid)
    np.testing.assert_array_equal(after.terms.node_owners, before.terms.node_owners)
    np.testing.assert_array_equal(after.prefpod.owner_weight, before.prefpod.owner_weight)
    # a PENDING pod with the same term still raises
    with pytest.raises(OverflowError):
        st.builder.build_from_state(st, [bad])


def test_two_profiles_over_one_cluster_state_agree():
    rng = np.random.default_rng(11)
    first = TPUBatchScheduler()
    second = TPUBatchScheduler(state=first.state)
    assert second.state is first.state and second.builder is first.builder
    for nd in _index_nodes(16):
        first.add_node(nd)
    for i in range(60):
        tpu = first if i % 2 else second
        tpu.assume(_template_pod(rng, f"b{i}"), f"n{int(rng.integers(0, 16))}")
    for key in list(first.state._pods)[::3]:
        second.forget(first.state._pods[key])
    _, mixed = _batches(rng)
    a = _assert_tables_match_bulk(first.state, mixed)
    b, _ = second.builder.build_from_state(second.state, mixed)
    for part in ("spread", "terms", "prefpod"):
        for x, y in zip(getattr(a, part), getattr(b, part)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# -- what an encode costs, in counts (valid on a CPU: never a time) ----------

def _state_with_bound(n_bound, n_nodes=50):
    st = schema.ClusterState(schema.SnapshotBuilder())
    for nd in _index_nodes(n_nodes):
        st.add_node(nd)
    for i in range(n_bound):
        st.add_pod(make_pod(f"b{i}", namespace=f"ns{i % 16}").req(cpu_milli=1, mem=MI)
                   .label("app", _APPS[i % 3]).obj(), f"n{i % n_nodes}")
    return st


def _encode_counting_calls(st, pending):
    """(Python and C calls made inside one build_from_state, the
    sched.encode.constraints span's n, a0, a1)."""
    st.builder.build_from_state(st, pending)       # vocabularies and spec store warm
    calls = 0

    def count(frame, event, arg):
        nonlocal calls
        if event in ("call", "c_call"):
            calls += 1

    t0 = time.perf_counter()
    # no collection inside: whether one falls there follows what every other
    # thread of the process allocates, and its callback's calls would count
    gc.disable()
    sys.setprofile(count)
    try:
        st.builder.build_from_state(st, pending)
    finally:
        sys.setprofile(None)
        gc.enable()
    rows = [dict(zip(trace.SPAN_FIELDS, r))
            for r in trace.snapshot(t0, float("inf"))["spans"]]
    span, = [r for r in rows if r["name"] == "sched.encode.constraints"]
    return calls, (span["n"], span["a0"], span["a1"])


def test_a_constraint_free_batch_reads_no_bound_entry_whatever_the_fill():
    pending = [make_pod(f"p{i}").req(cpu_milli=1, mem=MI).label("app", "web").obj()
               for i in range(16)]
    small, large = _state_with_bound(500), _state_with_bound(5000)
    calls_small, read_small = _encode_counting_calls(small, pending)
    calls_large, read_large = _encode_counting_calls(large, pending)
    assert read_small == (0, 500, 48) and read_large == (0, 5000, 48)
    assert calls_small == calls_large      # nothing is called once a bound pod


def test_a_spread_row_reads_the_arrays_and_calls_nothing_per_bound_pod():
    pending = [make_pod(f"p{i}").req(cpu_milli=1, mem=MI).label("app", "web")
               .spread(topology_key=api.LABEL_ZONE, selector={"app": "web"}).obj()
               for i in range(16)]
    small, large = _state_with_bound(500), _state_with_bound(5000)
    calls_small, read_small = _encode_counting_calls(small, pending)
    calls_large, read_large = _encode_counting_calls(large, pending)
    assert read_small == (500, 500, 48) and read_large == (5000, 5000, 48)
    assert calls_small == calls_large      # per signature (48 on both), not per pod
    snap, _ = large.builder.build_from_state(large, pending)
    # pods of app "web" in namespace "default": none bound, so the one row
    # counted nothing; the same row in the bound pods' namespace counts them
    assert snap.spread.valid.sum() == 1 and not snap.spread.node_matches.any()
    in_ns = [make_pod("q", namespace="ns0").req(cpu_milli=1).label("app", "web")
             .spread(topology_key=api.LABEL_ZONE, selector={"app": "web"}).obj()]
    snap, _ = large.builder.build_from_state(large, in_ns)
    want = sum(1 for i in range(5000) if i % 16 == 0 and i % 3 == 0)
    assert snap.spread.node_matches.sum() == want


def test_live_signatures_return_to_their_start_after_unique_labels_come_and_go():
    st = _state_with_bound(200)
    start = st.bound.live_signatures
    ids_before = len(st.bound.signatures)
    pods = [make_pod(f"job{i}").req(cpu_milli=1).label("job-name", f"j{i}").obj()
            for i in range(1000)]
    for wave in range(4):           # 250 at a time: the ids are handed on
        for p in pods[wave * 250:(wave + 1) * 250]:
            st.add_pod(p, "n1")
        assert st.bound.live_signatures == start + 250
        for p in pods[wave * 250:(wave + 1) * 250]:
            st.remove_pod(p)
    assert st.bound.live_signatures == start
    assert len(st.bound.signatures) == ids_before + 250     # not + 1,000
    _assert_index_coherent(st)
