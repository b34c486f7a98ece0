"""Operator gauges are bound once to their owner and read when read
(docs/scheduler_loop.md, "Operator metrics"): the scheduling loop pushes
none.  Four kinds of case:

  * a Gauge alone: a bound source is what get(), total, the snapshot()
    entry and the exposition report, with no cycle running; a labelled
    source renders one series a label; a source that raises serves the
    last good value and the exposition still renders;
  * name parity: after a seeded 20-cycle toy run of a Scheduler over a
    journaled Store, /metrics exposes the series it exposed when the
    loop pushed the gauges (SERIES, frozen from that tree);
  * value parity: after that run every bound gauge reads what its owner
    reports;
  * counts, valid from a CPU: those 20 cycles perform no Gauge.set and
    no Store.watch_stats() call, and build or load no executable after
    warm-up (with GRAFTLINT_SHAPES/COHERENCE/OBLIGATIONS armed, as
    `make audit` arms them: no steady-state retrace, no coherence
    violation, no double discharge).
"""

import logging
import random
import re
import sys
import time
import types
from unittest import mock

import pytest

from kubernetes_tpu.analysis import epochs, ledger, retrace
from kubernetes_tpu.api import store as st
from kubernetes_tpu.perf.collectors import MetricsCollector
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.scheduler.http import render_prometheus
from kubernetes_tpu.scheduler.metrics import Gauge, Registry
from kubernetes_tpu.testing.wrappers import GI, MI, make_node, make_pod
from kubernetes_tpu.utils import compileclock

CYCLES = 20
BATCH = 8


# -- a Gauge alone -------------------------------------------------------------


def test_bound_gauge_follows_its_source_without_a_cycle():
    reg = Registry()
    owner = types.SimpleNamespace(n=3)
    reg.mirror_delta_rows.bind(lambda: owner.n)
    entry = reg.snapshot()["scheduler_mirror_delta_rows"]
    assert entry is reg.mirror_delta_rows
    for n in (3, 17, 0):
        owner.n = n
        assert reg.mirror_delta_rows.get() == float(n)
        assert reg.mirror_delta_rows.total == float(n)
        assert entry.values() == {(): float(n)}
        assert f"scheduler_mirror_delta_rows {float(n)}\n" in render_prometheus(reg)
    rows = {r["labels"]["Metric"]: r["data"] for r in MetricsCollector(reg).collect()}
    assert "scheduler_mirror_delta_rows" not in rows     # 0: quiet
    owner.n = 5
    rows = {r["labels"]["Metric"]: r["data"] for r in MetricsCollector(reg).collect()}
    assert rows["scheduler_mirror_delta_rows"] == {"Total": 5.0}


def test_unbound_gauge_keeps_what_was_set():
    g = Gauge("g")
    assert g.get() == 0.0 and g.total == 0.0 and g.values() == {}
    g.set(4.0)
    g.set(2.0, "a")
    assert g.get() == 4.0 and g.get("a") == 2.0 and g.total == 6.0


def test_labelled_source_renders_one_series_a_label():
    reg = Registry()
    tiers = {"active": 2, "backoff": 0, "gated": 1}
    reg.pending_pods.bind(lambda: tiers)
    text = render_prometheus(reg)
    for tier, n in tiers.items():
        assert f'scheduler_pending_pods{{label0="{tier}"}} {float(n)}\n' in text
        assert reg.pending_pods.get(tier) == float(n)
    assert reg.pending_pods.total == 3.0
    tiers["active"] = 7
    assert reg.pending_pods.get("active") == 7.0


def test_source_that_raises_serves_the_last_good_value(caplog):
    reg = Registry()
    state = {"v": 12, "fail": False, "calls": 0}

    def source():
        state["calls"] += 1
        if state["fail"]:
            raise ConnectionError("replica set mid-failover")
        return state["v"]

    reg.apf_seats_current.bind(source)
    assert reg.apf_seats_current.get() == 12.0
    state["fail"] = True
    with caplog.at_level(logging.ERROR, logger="kubernetes_tpu.scheduler.metrics"):
        assert reg.apf_seats_current.get() == 12.0
        assert reg.apf_seats_current.total == 12.0
        assert "scheduler_apf_seats_current 12.0\n" in render_prometheus(reg)
    assert state["calls"] == 4
    logged = [r for r in caplog.records if "scheduler_apf_seats_current" in r.getMessage()]
    assert len(logged) == 1, "logged once, not once a scrape"
    state.update(fail=False, v=9)
    assert reg.apf_seats_current.get() == 9.0


def test_source_with_no_reading_leaves_the_stored_value():
    g = Gauge("g")
    g.bind(lambda: None)
    assert g.get() == 0.0
    g.set(3.0)
    assert g.get() == 3.0


def test_source_runs_outside_the_gauges_lock():
    g = Gauge("g")

    def source():
        free = g._lock.acquire(blocking=False)
        if free:
            g._lock.release()
        return float(free)

    g.bind(source)
    assert g.get() == 1.0, "the source could take the lock: get() did not hold it"


# -- the seeded toy run --------------------------------------------------------


def _series(text):
    """Sorted series names of an exposition: name and labels, a
    histogram's buckets folded into one `_bucket` series."""
    names = set()
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name = line.rsplit(" ", 1)[0]
        name = re.sub(r',?le="[^"]*"', "", name).replace("{}", "")
        names.add(name)
    return sorted(names)


def toy_run(journal_dir, scheduler_cls=Scheduler):
    """Warm-up, then CYCLES calls of schedule_batch() over a journaled
    store with Gauge.set, Store.watch_stats and compiles counted."""
    rng = random.Random(29)
    store = st.Store(journal_path=str(journal_dir / "journal"))
    for i in range(32):
        store.create(
            make_node(f"n{i}").capacity(cpu_milli=64000, mem=64 * GI, pods=110)
            .zone(f"z{i % 4}").obj()
        )

    def pod(name):
        return make_pod(name).req(cpu_milli=100, mem=64 * MI).obj()

    sched = scheduler_cls(store, batch_size=BATCH)
    sched.informers.informer("Node").start()
    sched.informers.informer("Pod").start()
    assert sched.informers.wait_for_sync(10)
    out = types.SimpleNamespace(sched=sched, store=store, sets=[], watch_stats_calls=0)
    try:
        sched.warmup([pod(f"warm-{i}") for i in range(BATCH)])
        for c in range(3):      # the first binds: bound-pod paths warm too
            for i in range(BATCH):
                store.create(pod(f"w{c}-{i}"))
            assert _cycle_until(sched, BATCH) == BATCH
            assert sched.flush_binds(timeout=30)

        real_set, real_ws = Gauge.set, st.Store.watch_stats

        def counted_set(self, value, *labels):
            out.sets.append((self.name, sys._getframe(1).f_code.co_name))
            return real_set(self, value, *labels)

        def counted_ws(self):
            out.watch_stats_calls += 1
            return real_ws(self)

        created = 3 * BATCH
        mark = compileclock.events()
        retrace.mark_steady()
        try:
            with mock.patch.object(Gauge, "set", counted_set), \
                    mock.patch.object(st.Store, "watch_stats", counted_ws):
                for c in range(CYCLES):
                    n = rng.randint(1, BATCH)
                    for i in range(n):
                        store.create(pod(f"p{c}-{i}"))
                    created += n
                    assert _cycle_until(sched, n) == n
            out.steady_retraces = retrace.steady_total()
        finally:
            retrace.clear_steady()
        out.compiled = compileclock.events() - mark
        assert sched.flush_binds(timeout=30)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and sched.cache.assumed_count():
            time.sleep(0.02)
        assert sched.cache.assumed_count() == 0
        pods, _ = store.list("Pod")
        assert len(pods) == created and all(p.spec.node_name for p in pods)
        out.exposition = render_prometheus(sched.metrics)
    except BaseException:
        sched.stop()
        store.close()
        raise
    return out


def _cycle_until(sched, n, cycles=50):
    """Calls of schedule_batch() until n pods were staged (the informer
    may deliver a cycle's pods over two pops)."""
    staged = 0
    for _ in range(cycles):
        staged += sched.schedule_batch(timeout=0.5).get("scheduled", 0)
        if staged >= n:
            break
    return staged


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    out = toy_run(tmp_path_factory.mktemp("metrics-sources"))
    try:
        yield out
    finally:
        out.sched.stop()
        out.store.close()


# frozen from the tree whose loop pushed the gauges (PR 28, d9bb8c5): the
# sorted output of _series(toy_run(...).exposition) there, less
# scheduler_c6s_arrival_knee_pods_per_s: nothing wrote it, and it went
SERIES = [
    'scheduler_apf_rejected_total',
    'scheduler_apf_seats_current',
    'scheduler_atomicity_findings',
    'scheduler_batch_solve_duration_seconds_bucket',
    'scheduler_batch_solve_duration_seconds_count',
    'scheduler_batch_solve_duration_seconds_sum',
    'scheduler_batch_window_ms',
    'scheduler_binder_poison_waves_total',
    'scheduler_binder_restarts_total',
    'scheduler_coherence_audits_total',
    'scheduler_coherence_violations_total',
    'scheduler_commit_subwave_duration_seconds_bucket',
    'scheduler_commit_subwave_duration_seconds_count',
    'scheduler_commit_subwave_duration_seconds_sum',
    'scheduler_commit_subwave_overlap_seconds_bucket',
    'scheduler_commit_subwave_overlap_seconds_count',
    'scheduler_commit_subwave_overlap_seconds_sum',
    'scheduler_commit_wave_duration_seconds_bucket',
    'scheduler_commit_wave_duration_seconds_count',
    'scheduler_commit_wave_duration_seconds_sum',
    'scheduler_commit_wave_size_pods_bucket',
    'scheduler_commit_wave_size_pods_count',
    'scheduler_commit_wave_size_pods_sum',
    'scheduler_compaction_moved_rows',
    'scheduler_compactions_total',
    'scheduler_decode_overlap_seconds_bucket',
    'scheduler_decode_overlap_seconds_count',
    'scheduler_decode_overlap_seconds_sum',
    'scheduler_encode_rows_per_s',
    'scheduler_fanout_chunk_size',
    'scheduler_fenced_writes_total',
    'scheduler_fragmentation_score',
    'scheduler_framework_extension_point_duration_seconds_bucket{extension_point="Permit"}',
    'scheduler_framework_extension_point_duration_seconds_bucket{extension_point="PostBind"}',
    'scheduler_framework_extension_point_duration_seconds_bucket{extension_point="PreBind"}',
    'scheduler_framework_extension_point_duration_seconds_bucket{extension_point="PreEnqueue"}',
    'scheduler_framework_extension_point_duration_seconds_bucket{extension_point="Reserve"}',
    'scheduler_framework_extension_point_duration_seconds_count{extension_point="Permit"}',
    'scheduler_framework_extension_point_duration_seconds_count{extension_point="PostBind"}',
    'scheduler_framework_extension_point_duration_seconds_count{extension_point="PreBind"}',
    'scheduler_framework_extension_point_duration_seconds_count{extension_point="PreEnqueue"}',
    'scheduler_framework_extension_point_duration_seconds_count{extension_point="Reserve"}',
    'scheduler_framework_extension_point_duration_seconds_sum{extension_point="Permit"}',
    'scheduler_framework_extension_point_duration_seconds_sum{extension_point="PostBind"}',
    'scheduler_framework_extension_point_duration_seconds_sum{extension_point="PreBind"}',
    'scheduler_framework_extension_point_duration_seconds_sum{extension_point="PreEnqueue"}',
    'scheduler_framework_extension_point_duration_seconds_sum{extension_point="Reserve"}',
    'scheduler_gang_contiguous_placements_total',
    'scheduler_interleave_schedules_total',
    'scheduler_interleave_yield_points',
    'scheduler_journal_frame_bytes',
    'scheduler_journal_recovered_records',
    'scheduler_lane_count',
    'scheduler_leader_reconcile_total',
    'scheduler_mirror_delta_rows',
    'scheduler_mirror_grow_rows',
    'scheduler_mirror_grow_total',
    'scheduler_mirror_resync_total',
    'scheduler_misspeculation_total',
    'scheduler_node_axis_bucket',
    'scheduler_obligation_double_discharge_total',
    'scheduler_obligation_leaks_total',
    'scheduler_obligations_tracked_total',
    'scheduler_overload_level',
    'scheduler_overload_shed_total',
    'scheduler_partials_full_recomputes_total',
    'scheduler_partials_hit_rows',
    'scheduler_partials_recomputed_rows',
    'scheduler_partials_rollbacks_total',
    'scheduler_pending_pods{label0="active"}',
    'scheduler_pending_pods{label0="backoff"}',
    'scheduler_pending_pods{label0="gang_staged"}',
    'scheduler_pending_pods{label0="gated"}',
    'scheduler_pending_pods{label0="inflight"}',
    'scheduler_pending_pods{label0="unschedulable"}',
    'scheduler_pipeline_overlap_seconds_bucket',
    'scheduler_pipeline_overlap_seconds_count',
    'scheduler_pipeline_overlap_seconds_sum',
    'scheduler_pod_scheduling_sli_duration_seconds_bucket',
    'scheduler_pod_scheduling_sli_duration_seconds_count',
    'scheduler_pod_scheduling_sli_duration_seconds_sum',
    'scheduler_preemption_attempts_total',
    'scheduler_preemption_batch_size_pods_bucket',
    'scheduler_preemption_batch_size_pods_count',
    'scheduler_preemption_batch_size_pods_sum',
    'scheduler_preemption_conflict_serializations_total',
    'scheduler_preemption_pdb_blocked_total',
    'scheduler_preemption_solve_duration_seconds_bucket',
    'scheduler_preemption_solve_duration_seconds_count',
    'scheduler_preemption_solve_duration_seconds_sum',
    'scheduler_preemption_victims_bucket',
    'scheduler_preemption_victims_count',
    'scheduler_preemption_victims_sum',
    'scheduler_replica_failovers_total',
    'scheduler_schedule_attempts_total{label0="scheduled"}',
    'scheduler_schedule_batch_duration_seconds_bucket',
    'scheduler_schedule_batch_duration_seconds_count',
    'scheduler_schedule_batch_duration_seconds_sum',
    'scheduler_scheduling_algorithm_duration_seconds_bucket',
    'scheduler_scheduling_algorithm_duration_seconds_count',
    'scheduler_scheduling_algorithm_duration_seconds_sum',
    'scheduler_scheduling_attempt_duration_seconds_bucket',
    'scheduler_scheduling_attempt_duration_seconds_count',
    'scheduler_scheduling_attempt_duration_seconds_sum',
    'scheduler_server_watch_write_stalls_total',
    'scheduler_sharded_solve_fallbacks',
    'scheduler_slice_carveout_fallbacks_total',
    'scheduler_slice_carveouts_total',
    'scheduler_solve_breaker_state',
    'scheduler_solve_compile_duration_seconds_bucket',
    'scheduler_solve_compile_duration_seconds_count',
    'scheduler_solve_compile_duration_seconds_sum',
    'scheduler_solve_fallback_total',
    'scheduler_solve_retrace_total',
    'scheduler_solve_shard_count',
    'scheduler_solve_wave_count_bucket',
    'scheduler_solve_wave_count_count',
    'scheduler_solve_wave_count_sum',
    'scheduler_solve_wave_fallbacks_bucket',
    'scheduler_solve_wave_fallbacks_count',
    'scheduler_solve_wave_fallbacks_sum',
    'scheduler_solve_wave_steps_bucket',
    'scheduler_solve_wave_steps_count',
    'scheduler_solve_wave_steps_sum',
    'scheduler_speculative_solves_total',
    'scheduler_store_checkpoints_total',
    'scheduler_store_journal_suffix_records',
    'scheduler_store_recovery_duration_ms',
    'scheduler_store_shard_count',
    'scheduler_store_snapshot_records',
    'scheduler_subwave_stream_lead_ms_bucket',
    'scheduler_subwave_stream_lead_ms_count',
    'scheduler_subwave_stream_lead_ms_sum',
    'scheduler_watch_coalesced_total',
    'scheduler_watch_expired_total',
    'scheduler_watch_queue_depth',
    'scheduler_watch_terminated_total',
]


def test_metrics_name_parity(run):
    assert _series(run.exposition) == SERIES


# -- value parity: each gauge reads what its owner reports ---------------------


def _mirror(s):
    ms = s.tpu._mirror.stats()
    return {
        "mirror_resync_total": ms["resync_total"],
        "mirror_delta_rows": ms["delta_rows_total"],
        "mirror_grow_total": ms["grow_syncs"],
        "mirror_grow_rows": ms["grow_rows_total"],
    }


def _partials(s):
    ps = [fwk.tpu._partials.stats() for fwk in s.profiles]
    return {
        "partials_hit_rows": sum(p["hit_rows_total"] for p in ps),
        "partials_recomputed_rows": sum(p["recomputed_rows_total"] for p in ps),
        "partials_full_recomputes": sum(p["full_recomputes"] for p in ps),
        "partials_rollbacks": sum(p["rollbacks"] for p in ps),
    }


def _watch(s):
    ws = s.store.watch_stats()
    return {k: ws[k] for k in (
        "watch_queue_depth", "watch_coalesced_total", "watch_expired_total")}


def _store(s):
    store = s.store
    return {
        "journal_frame_bytes": store.journal_frame_bytes,
        "journal_recovered_records": store.journal_recovered_records,
        "store_recovery_duration_ms": store.recovery_duration_ms,
        "store_snapshot_records": store.snapshot_records,
        "store_journal_suffix_records": store.journal_suffix_records,
        "store_checkpoints_total": store.checkpoints_total,
        "store_shard_count": store.shard_count,
        "fenced_writes_total": store.fenced_writes_total,
        "fanout_chunk_size": store.fanout_chunk_events / max(store.fanout_chunks, 1),
    }


def _solver(s):
    return {
        "solve_breaker_state": s.tpu.breaker.state_code(),
        "solve_fallback_total": s.tpu.breaker.fallback_count(),
        "solve_shard_count": s.tpu.shard_count,
        "sharded_solve_fallbacks": s.tpu.sharded_fallbacks,
        "node_axis_bucket": s.tpu.state.node_axis_bucket,
        "compactions_total": s.tpu.state.compactions_total,
        "compaction_moved_rows": s.tpu.state.compaction_moved_rows_total,
        "encode_rows_per_s": s.tpu.last_encode_rows_per_s,
    }


def _loop(s):
    return {
        "overload_level": s.overload.level(),
        "batch_window_ms": s.window_ctl.window() * 1000.0,
        "lane_count": 1,
    }


def _auditors(s):
    return {
        "solve_retrace_total": retrace.total(),
        "coherence_audits": epochs.audits_total(),
        "coherence_violations": epochs.violations_total(),
        "obligations_tracked": ledger.tracked_total(),
        "obligation_leaks": ledger.leaks_total(),
        "obligation_double_discharge": ledger.double_discharge_total(),
    }


OWNERS = {
    "mirror": _mirror, "partials": _partials, "watch": _watch, "store": _store,
    "solver": _solver, "loop": _loop, "auditors": _auditors,
}
# gauges that must have moved in the toy run: the parity is not 0 == 0
MOVED = {
    "mirror": ("mirror_resync_total", "mirror_delta_rows"),
    "partials": ("partials_recomputed_rows", "partials_full_recomputes"),
    "watch": (),
    "store": ("journal_frame_bytes", "store_shard_count", "fanout_chunk_size"),
    "solver": ("node_axis_bucket", "encode_rows_per_s"),
    "loop": ("batch_window_ms", "lane_count"),
    "auditors": (),
}


@pytest.mark.parametrize("owner", sorted(OWNERS))
def test_gauge_reads_what_its_owner_reports(run, owner):
    m = run.sched.metrics
    read = {name: getattr(m, name).get() for name in OWNERS[owner](run.sched)}
    want = OWNERS[owner](run.sched)
    assert read == {k: float(v) for k, v in want.items()}
    for name in MOVED[owner]:
        assert read[name] > 0.0, name
    # the collectors' export goes through the same sources
    rows = {r["labels"]["Metric"]: r["data"] for r in MetricsCollector(m).collect()}
    for name, v in read.items():
        series = getattr(m, name).name
        assert rows.get(series, {"Total": 0.0}) == {"Total": v}, series


def test_labelled_gauges_read_their_owners(run):
    m, sched = run.sched.metrics, run.sched
    qs = sched.queue.stats()
    assert m.pending_pods.values() == {(tier,): float(v) for tier, v in qs.items()}
    assert m.pending_pods.total == float(sum(qs.values())) == 0.0
    for i in range(3):
        sched.queue.add(make_pod(f"late-{i}").req(cpu_milli=100).obj())
    assert m.pending_pods.get("active") == 3.0, "no cycle ran: the read asked the queue"
    assert 'scheduler_pending_pods{label0="active"} 3.0\n' in render_prometheus(m)
    assert m.watch_terminated_total.values() == {
        (kind,): float(n) for kind, n in run.store.terminated_by_kind.items()}


def test_serving_gauges_have_no_reading_without_a_plane(run):
    m = run.sched.metrics
    assert getattr(run.store, "serving_plane", None) is None
    for g in (m.apf_seats_current, m.apf_rejected_total,
              m.server_watch_write_stalls_total, m.replica_failovers_total):
        assert g.values() == {} and g.get() == 0.0


# -- counts --------------------------------------------------------------------


def test_the_loop_sets_no_gauge(run):
    assert [s for s in run.sets if s[1] == "_finish_cycle"] == []
    assert run.sets == [], "nor does any other part of a plain cycle"


def test_the_loop_never_calls_watch_stats(run):
    assert run.watch_stats_calls == 0


def test_nothing_built_after_warmup_and_the_auditors_are_clean(run):
    assert run.compiled == 0
    assert run.steady_retraces == 0        # 0 when GRAFTLINT_SHAPES is unset
    m = run.sched.metrics
    assert m.coherence_violations.get() == 0.0
    assert m.obligation_double_discharge.get() == 0.0
    if epochs.active() is not None:
        assert m.coherence_audits.get() > 0.0
    if ledger.active() is not None:
        assert m.obligations_tracked.get() > 0.0
    if retrace.active() is not None:
        assert m.solve_retrace_total.get() > 0.0
