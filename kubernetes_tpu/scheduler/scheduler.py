"""The host scheduler: informer-fed cache + queue draining into batched
device solves, with a two-stage solve/bind pipeline.

Reference mapping (pkg/scheduler/scheduler.go, schedule_one.go):

  Scheduler.run            scheduler.go:438 Run (queue flush + hot loop)
  schedule_batch           the batched schedule_one.go:66 ScheduleOne:
                           NextPod -> schedulePod -> assume; one device
                           dispatch schedules the whole batch.  The bind
                           tail is handed to the binding stage as a WAVE
                           and commits off-thread.
  binding stage            schedule_one.go:118's `go bindingCycle` —
                           binds never run on the scheduling thread.
                           Ours is a dedicated worker committing whole
                           waves through one store transaction
                           (store.update_wave) instead of per-pod
                           goroutines doing per-pod POSTs; assume-cache
                           entries bridge the gap exactly as the
                           reference's assume/bind split does, so batch
                           N+1's snapshot is correct while batch N's
                           binds are still in flight.
  failure handling         handleSchedulingFailure :1017 ->
                           AddUnschedulableIfNotPresent; a bind error
                           splits that pod out of the wave, forgets the
                           assume and requeues with backoff
  event wiring             eventhandlers.go:287 addAllEventHandlers:
                           informers feed cache (assigned pods, nodes)
                           and queue (pending pods, requeue-on-event)

The scheduling algorithm itself — filters, scores, selectHost, the
assume bookkeeping between pods of one batch — runs on the TPU inside
TPUBatchScheduler (models/batch_scheduler.py).
"""

from __future__ import annotations

import copy
import logging
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional

import numpy as np

from ..analysis import epochs as _epochs
from ..analysis import ledger as _ledger
from ..analysis import retrace as _retrace
from ..api import store as st
from ..api import types as api
from ..client.events import EventRecorder
from ..client.informers import InformerFactory
from ..models.batch_scheduler import TPUBatchScheduler, device_label
from ..ops import assign as assign_ops
from ..testing import faults
from ..utils import compileclock
from ..utils import trace as _trace
from .cache import SchedulerCache
from .config import SchedulerConfiguration
from .framework import Framework, FrameworkRegistry
from .metrics import Registry
from .preemption import PreemptionEvaluator
from .queue import AdaptiveBatchWindow, QueuedPodInfo, SchedulingQueue, pod_key
from .waitingpods import WaitingPod, WaitingPodsMap


class OverloadController:
    """Load-aware degradation ladder for the solve stage.

    Tracks an EWMA of the cycle's PLACEMENT work (pop → solve → stage →
    dispatch) against the latency SLO and exposes a shed level consumed
    each cycle.  The PostFilter preemption pass is EXCLUDED from the
    fed duration: shedding decisions must not be driven by the work
    they shed — counting the pass made one expensive preemption round
    trip the ladder to level 2, which deferred preemption, which left
    no cycles to decay the average: preemption froze exactly when the
    backlog needed it (self-inhibition).  A cycle
    that had to BUILD OR LOAD AN EXECUTABLE is not fed at all
    (utils/compileclock): a first-of-a-bucket cycle blocks for seconds
    in trace + XLA compile — or the persistent cache's load — and says
    nothing about load; fed to the ladder, a cold start on the chip
    read as severe overload and an otherwise idle cluster had no later
    cycle to bring the level down, so preemptors waited for the
    unschedulable flush.  The price: while every cycle compiles (a
    retrace storm) the ladder learns nothing and holds its level.

      0  healthy — full work;
      1  overloaded (ewma > slo) — background work sheds first: the
         PostFilter preemption BATCH is capped (the batched dry-run
         amortized the per-pod marginal cost, so an overloaded cycle
         keeps a small batch instead of deferring preemption outright —
         preemption load spikes exactly when the cluster is overloaded);
         pods past the cap count into scheduler_overload_shed_total and
         retry with backoff (queue.retry_parked) — it is never the
         placement work itself that is shed;
      2  severe (ewma > 2*slo) — preemption dry-runs defer entirely and
         the adaptive batch window pins at its max: fewer, fuller
         cycles shed per-cycle fixed overhead without dropping pods.

    Levels fall only when the EWMA drops below 80% of the rising
    threshold (hysteresis), so one fast cycle doesn't flap the ladder.
    The average moves only when a cycle runs, so a cluster that goes
    idle at a raised level would hold it for good with the shed pods
    parked behind it: their backoff retries are the cycles that bring it
    down (a 3,900-pod burst on the chip's shared host reached level 2 by
    real staging time, and 8 preemptors sent behind it waited for the
    300 s unschedulable flush).
    """

    GUARDED_FIELDS = {"_ewma": "_lock", "_level": "_lock"}

    _ALPHA = 0.3

    def __init__(self, slo_seconds: float = 0.5):
        self.slo = slo_seconds
        self._lock = threading.Lock()
        self._ewma = 0.0
        self._level = 0

    def note_cycle(self, duration_s: float) -> int:
        with self._lock:
            self._ewma += self._ALPHA * (max(duration_s, 0.0) - self._ewma)
            e, lvl = self._ewma, self._level
            if e > 2 * self.slo:
                lvl = 2
            else:
                if lvl == 2 and e < 0.8 * 2 * self.slo:
                    lvl = 1
                if e > self.slo:
                    lvl = max(lvl, 1)
                elif lvl == 1 and e < 0.8 * self.slo:
                    lvl = 0
            self._level = lvl
            return lvl

    def level(self) -> int:
        with self._lock:
            return self._level


def _combine_transforms(transforms):
    """Compose pod_transform hooks: selectors AND together, extra
    requests sum (VolumeBinding + DRA both fold into the encode)."""

    def combined(pod):
        selector, requests = None, {}
        for fn in transforms:
            sel, extra = fn(pod)
            selector = api.and_selectors(selector, sel)
            for k, v in (extra or {}).items():
                requests[k] = requests.get(k, 0) + v
        return selector, requests

    return combined


class _Cycle:
    """One in-flight solve-stage cycle: popped-batch staging state plus
    (optionally) the last profile group still out on the device as a
    DeviceSolve future (scheduler._run's readback pipeline).

    `batch` is every popped info and `handled` the keys a terminal path
    has taken ownership of (staged into the wave, parked, requeued,
    handed to a Permit thread): a cycle that dies mid-flight is salvaged
    by requeueing batch − handled, so a fault can never strand pods in
    the 'inflight' tier (Scheduler._salvage_cycle)."""

    __slots__ = ("stats", "trace", "reservations", "failed", "wave",
                 "pending", "solved_any", "batch", "handled",
                 "spec_token", "mirror_points", "partials_points",
                 "compile_mark", "fail_n", "fail_parked", "fail_s",
                 "fail_t0", "fail_t1")

    def __init__(self, stats, trace, reservations, batch):
        self.stats = stats
        self.trace = trace
        # the lane thread's compile count when the trace started: the
        # cycle's dispatch and finish halves run on that one thread
        self.compile_mark = compileclock.events()
        self.reservations = reservations
        self.failed: List[QueuedPodInfo] = []
        self.wave: List[tuple] = []
        self.pending = None  # (fwk, sched_name, group, DeviceSolve, t_solve)
        self.solved_any = False
        self.batch: List[QueuedPodInfo] = batch
        self.handled: set = set()
        # speculative dispatch: the wave-failure generation this cycle's
        # solves were dispatched under (None = not speculative), plus
        # per-profile mirror AND partials-cache bookmarks for the
        # invalidation rollback (the two resident buffers roll together)
        self.spec_token = None
        self.mirror_points: Dict[str, tuple] = {}
        self.partials_points: Dict[str, tuple] = {}
        # the failure branch's tally (the sched.fail row _finish_cycle
        # writes): pods that ended without a bind, how many of them were
        # parked, the seconds their branches took, first start, last end
        self.fail_n = self.fail_parked = 0
        self.fail_s = self.fail_t0 = self.fail_t1 = 0.0

    def note_failure(self, t0: float, parked: bool = False) -> None:
        """One pod's attempt ended without a bind: the branch that began
        at `t0` (before its ``_mark_failed``) has just parked it
        (`parked`) or put it back on the queue."""
        t1 = _trace.now()
        if not self.fail_n:
            self.fail_t0 = t0
        self.fail_t1 = t1
        self.fail_n += 1
        self.fail_parked += bool(parked)
        self.fail_s += t1 - t0


_REASON_TEXT = {
    assign_ops.REASON_STATIC: "node affinity/taints/name mismatch",
    assign_ops.REASON_RESOURCES: "insufficient resources",
    assign_ops.REASON_PORTS: "host port conflict",
    assign_ops.REASON_SPREAD: "topology spread constraints violated",
    assign_ops.REASON_INTERPOD: "inter-pod (anti-)affinity rules",
    assign_ops.REASON_GANG: "gang not fully placeable",
    assign_ops.REASON_SLICE: "no free contiguous slice carve-out",
}


class Scheduler:
    # graftlint guarded-by declarations: the binding-stage backlog and
    # worker flags share the wave condition; the device-solve interval
    # log (pipeline-overlap attribution) shares the solve lock
    GUARDED_FIELDS = {
        "_waves": "_wave_cv",
        "_wave_active": "_wave_cv",
        "_binder_stop": "_wave_cv",
        "_stream_inflight": "_wave_cv",
        "_solve_windows": "_solve_lock",
        "_solve_open": "_solve_lock",
        "_wave_fail_gen": "_spec_lock",
        "_inflight_cycles": "_inflight_lock",
    }

    def __init__(
        self,
        store: st.Store,
        batch_size: Optional[int] = None,
        tpu: Optional[TPUBatchScheduler] = None,
        assume_ttl: Optional[float] = None,
        clock=time.monotonic,
        leader_elector=None,
        config: Optional[SchedulerConfiguration] = None,
    ):
        self.store = store
        self.config = (config or SchedulerConfiguration()).validate()
        self.batch_size = batch_size or self.config.batch_size
        # profiles: scheduler_name -> Framework, one shared cluster state
        # (profile/profile.go:46; explicit `tpu` keeps the single-profile
        # constructor shape tests/benches use)
        self.profiles = FrameworkRegistry(
            self.config, state=tpu.state if tpu else None
        )
        if tpu is not None:
            # the injected instance IS the default profile's solver —
            # sharing only its state would silently drop a custom
            # mode/score_config/limits on the scheduling path (the
            # registry-built instance would solve instead)
            self.profiles.default.tpu = tpu
        self.tpu = tpu or self.profiles.default.tpu
        self.cache = SchedulerCache(
            self.tpu.state,
            ttl=assume_ttl or self.config.assume_ttl_seconds,
            clock=clock,
        )
        # overload protection (docs/robustness.md): the adaptive window
        # sizes pop_batch's accumulation from observed arrival rate and
        # solve/commit cost; the overload controller sheds background
        # work (preemption dry-runs) and widens the window when cycles
        # overrun the latency SLO, instead of letting traces pile up
        self.window_ctl: Optional[AdaptiveBatchWindow] = None
        if self.config.adaptive_batch_window:
            self.window_ctl = AdaptiveBatchWindow(
                base_window=self.config.batch_window_seconds,
                min_window=self.config.batch_window_min_seconds,
                max_window=self.config.batch_window_max_seconds,
                slo_seconds=self.config.batch_latency_slo_seconds,
                clock=clock,
            )
        self.overload = OverloadController(
            slo_seconds=self.config.batch_latency_slo_seconds
        )
        self.queue = SchedulingQueue(
            backoff_base=self.config.pod_initial_backoff_seconds,
            backoff_max=self.config.pod_max_backoff_seconds,
            unschedulable_flush_after=self.config.unschedulable_flush_seconds,
            clock=clock,
            batch_window=self.config.batch_window_seconds,
            window_ctl=self.window_ctl,
        )
        self.metrics = Registry()
        self._bind_gauges()
        # pods parked at Permit (waiting_pods_map.go); coscheduling-style
        # plugins Allow/Reject through this map
        self.waiting = WaitingPodsMap()
        # async: a bind wave must not pay per-pod synchronous Event
        # writes on the scheduling thread (the broadcaster channel)
        self.events = EventRecorder(
            store, component="default-scheduler", async_mode=True
        )
        self.preemption = PreemptionEvaluator(
            self.tpu, self.cache, store, self.metrics
        )
        self.preemption.events = self.events
        # PostFilter budget per cycle: preemption is the exceptional path;
        # cap the per-batch dry-run work so a mass of unschedulable pods
        # can't stall the hot loop.
        self.max_preemptions_per_cycle = self.config.max_preemptions_per_cycle
        # VolumeBinding: host-side claim/volume state; topology + attach
        # limits fold into the snapshot encode via the builder transform
        # (scheduler/volumebinding.py) — PreFilter/Filter cost nothing
        # extra on device.  Reserve rides filter_result, rollback rides
        # unreserve, API writes ride pre_bind.
        from .deviceclaims import DeviceClaimBinder
        from .volumebinding import VolumeBinder

        gate = self.profiles.gate
        self.preemption.pdb_aware = gate.enabled("PDBAwarePreemption")
        self.volumes = VolumeBinder(store)
        self.devices = DeviceClaimBinder(store)
        transforms = []
        if gate.enabled("VolumeBinding"):
            transforms.append(self.volumes.pod_requirements)
        if gate.enabled("DynamicResourceAllocation"):
            transforms.append(self.devices.pod_requirements)
            # topology-shaped claims hand their carve-out extent to the
            # encoder (the batched carve-out kernels steer the carrier
            # onto a free-box corner; scheduler/deviceclaims.py)
            self.tpu.builder.pod_shape_hook = self.devices.pod_shape
        if transforms:
            self.tpu.builder.pod_transform = _combine_transforms(transforms)
        # default plugins on every profile: preemption (PostFilter) +
        # volume binding + device claims (Reserve/Unreserve/PreBind)
        for fwk in self.profiles:
            fwk.metrics = self.metrics
            # background prewarm compiles report into the same histogram
            # as synchronous first-shape compiles
            pool = getattr(fwk.tpu, "prewarm_pool", None)
            if pool is not None:
                pool.compile_observer = (
                    self.metrics.solve_compile_duration.observe
                )
            fwk.post_filter.append(self._preempt_plugin)
            if gate.enabled("VolumeBinding"):
                fwk.filter_result.append(self._volume_reserve_plugin)
                fwk.unreserve.append(self.volumes.unreserve)
                fwk.pre_bind.append(self.volumes.prebind)
            if gate.enabled("DynamicResourceAllocation"):
                fwk.filter_result.append(self._device_reserve_plugin)
                fwk.unreserve.append(self.devices.unreserve)
                fwk.pre_bind.append(self.devices.prebind)
        self.informers = InformerFactory(store)
        # Optional client.leaderelection.LeaderElector: when set, the hot
        # loop only schedules while leading (app/server.go:170-180 —
        # replicated schedulers, single active) — standbys keep informers
        # warm so takeover is immediate.
        self.leader_elector = leader_elector
        # Leadership/restart reconciliation (docs/robustness.md): the
        # flag starts SET so the first leading pass of the hot loop
        # reconciles local pipeline state against the store — covering
        # process restart AND an elector that acquired before this
        # scheduler attached; every later acquisition re-sets it.  The
        # reconcile itself runs on the scheduling thread (never the
        # elector thread, whose renew cadence it must not delay).
        self._reconcile_needed = threading.Event()
        self._reconcile_needed.set()
        if leader_elector is not None:
            prev_cb = leader_elector.on_started_leading

            def _on_started_leading():
                self._reconcile_needed.set()
                if prev_cb:
                    prev_cb()

            leader_elector.on_started_leading = _on_started_leading
        self._clock = clock
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # -- pipelined multi-lane scheduling ------------------------------
        # each lane runs its own pop→encode→solve pipeline over its
        # profiles' disjoint pod classes (docs/scheduler_loop.md); lane 0
        # is the LEAD lane (leadership reconcile, assume-TTL sweeps,
        # cross-cutting metric mirrors).  scheduler_lanes=0 auto-sizes to
        # one lane per profile; a single profile keeps the serial loop.
        names = list(self.profiles.frameworks)
        lanes_cfg = self.config.scheduler_lanes
        n_lanes = len(names) if lanes_cfg == 0 else min(lanes_cfg, len(names))
        n_lanes = max(n_lanes, 1)
        if n_lanes > 1:
            self._lane_profiles: List[Optional[set]] = [
                set(names[i::n_lanes]) for i in range(n_lanes)
            ]
        else:
            self._lane_profiles = [None]  # one lane pops every class
        self._lane_threads: List[threading.Thread] = []
        # a span's attribute for "which profile" (names stay a closed set)
        self._profile_ids = {name: i for i, name in enumerate(names)}
        self.metrics.lane_count.set(float(n_lanes))
        # per-scheduling-thread in-flight cycle (lanes + direct
        # schedule_batch callers salvage their OWN cycle on faults)
        self._inflight_lock = threading.Lock()
        self._inflight_cycles: Dict[int, "_Cycle"] = {}
        # speculative solve overlap: batches dispatched while a wave is
        # still committing record the wave-failure generation; a commit
        # failure/fence bumps it and invalidates the speculation
        self._speculation_enabled = self.config.speculative_solve
        self._spec_lock = threading.Lock()
        self._wave_fail_gen = 0
        # PostFilter preemption shares one evaluator: concurrent lanes
        # serialize their passes (preemption is background work)
        self._postfilter_lock = threading.Lock()
        # -- binding stage (the async binding cycle) ----------------------
        # schedule_batch stages placements (assume + Permit) and hands the
        # bind tail to this worker as a wave; the next cycle's pop/solve
        # overlaps the commit.  Backlog is bounded so a commit stage that
        # falls behind backpressures the solve stage instead of growing
        # an unbounded requeue-latency tail.
        self._waves: deque = deque()  # (entries, attempts) pairs
        self._wave_cv = threading.Condition()
        self._wave_active = False
        self._binder_stop = False
        self._max_wave_backlog = 2
        # device-solve intervals, for the pipeline-overlap metric (the
        # binder reads them to attribute its commit time)
        self._solve_lock = threading.Lock()
        self._solve_windows: deque = deque(maxlen=64)  # (start, end)
        self._solve_open: Optional[float] = None
        # sharded-store commit fan-out: the binder partitions each wave
        # into per-store-shard sub-waves and commits up to this many
        # concurrently (shard A's journal fsync / watch fan-out overlaps
        # shard B's and the next solve).  A 1-shard store keeps the
        # serial single-transaction path and pays for no pool.
        subwave_width = min(
            self.config.commit_subwave_concurrency,
            getattr(store, "shard_count", 1),
        )
        self._commit_pool: Optional[ThreadPoolExecutor] = (
            ThreadPoolExecutor(
                max_workers=subwave_width,
                thread_name_prefix="commit-subwave",
            )
            if subwave_width > 1
            else None
        )
        self._subwave_width = subwave_width
        # streamed sub-wave commits: staging hands each store shard's
        # slice of a wave to the commit pool AS IT STAGES, instead of
        # dispatching the whole wave after the full readback; bounded by
        # 2x the pool width (backpressure on the solve stage)
        self._stream_enabled = (
            self.config.stream_subwaves and self._commit_pool is not None
        )
        self._stream_inflight = 0
        self._bind_thread = threading.Thread(
            target=self._bind_worker, name="bind-wave", daemon=True
        )
        self._bind_thread.start()
        self._wire_handlers()

    def _bind_gauges(self) -> None:
        """Bind each operator gauge to its owner, once.  A gauge reads
        what its owner reports WHEN SOMEONE READS IT (/metrics, a
        collector, a test); the scheduling loop pushes none.  Sources
        run on the reader's thread: plain counters are read as they are,
        locked state through its owner's locked reader.  They go through
        `self.tpu` and `self.profiles`, so a swapped solver is followed.
        Timing is not here: it lives in the recorder (utils/trace.py)."""
        m, store = self.metrics, self.store
        m.pending_pods.bind(self.queue.stats)  # by tier
        m.overload_level.bind(self.overload.level)
        if self.window_ctl is not None:
            m.batch_window_ms.bind(
                lambda: self.window_ctl.window() * 1000.0
            )
        # degraded mode: the device-solve breaker
        m.solve_breaker_state.bind(lambda: self.tpu.breaker.state_code())
        m.solve_fallback_total.bind(
            lambda: self.tpu.breaker.fallback_count()
        )
        # the runtime auditors' totals (0 unless a GRAFTLINT_* armed them)
        m.solve_retrace_total.bind(_retrace.total)
        m.coherence_audits.bind(_epochs.audits_total)
        m.coherence_violations.bind(_epochs.violations_total)
        m.obligations_tracked.bind(_ledger.tracked_total)
        m.obligation_leaks.bind(_ledger.leaks_total)
        m.obligation_double_discharge.bind(_ledger.double_discharge_total)
        # sharded solve, device mirror, elastic node axis
        m.solve_shard_count.bind(lambda: self.tpu.shard_count)
        m.sharded_solve_fallbacks.bind(lambda: self.tpu.sharded_fallbacks)
        for gauge, key in (
            (m.mirror_resync_total, "resync_total"),
            (m.mirror_delta_rows, "delta_rows_total"),
            (m.mirror_grow_total, "grow_syncs"),
            (m.mirror_grow_rows, "grow_rows_total"),
        ):
            gauge.bind(lambda key=key: self.tpu._mirror.stats()[key])
        for gauge, attr in (
            (m.node_axis_bucket, "node_axis_bucket"),
            (m.compactions_total, "compactions_total"),
            (m.compaction_moved_rows, "compaction_moved_rows_total"),
        ):
            gauge.bind(lambda attr=attr: getattr(self.tpu.state, attr))
        # incremental solve: summed over every profile's cache (profiles
        # sync independently, the surface is one control plane)
        for gauge, key in (
            (m.partials_hit_rows, "hit_rows_total"),
            (m.partials_recomputed_rows, "recomputed_rows_total"),
            (m.partials_full_recomputes, "full_recomputes"),
            (m.partials_rollbacks, "rollbacks"),
        ):
            gauge.bind(lambda key=key: sum(
                fwk.tpu._partials.stats()[key]
                for fwk in self.profiles
                if fwk.tpu._partials is not None
            ))
        # the most recent snapshot build's encode rate (the max over
        # profiles: summed would double-count the shared builder)
        m.encode_rows_per_s.bind(lambda: max(
            fwk.tpu.last_encode_rows_per_s for fwk in self.profiles
        ))
        # the store: journal framing, the last recovery's cost split,
        # checkpoints, fenced late-leader waves, fan-out chunking
        for gauge, attr in (
            (m.journal_frame_bytes, "journal_frame_bytes"),
            (m.journal_recovered_records, "journal_recovered_records"),
            (m.store_recovery_duration_ms, "recovery_duration_ms"),
            (m.store_snapshot_records, "snapshot_records"),
            (m.store_journal_suffix_records, "journal_suffix_records"),
            (m.store_checkpoints_total, "checkpoints_total"),
            (m.store_shard_count, "shard_count"),
            (m.fenced_writes_total, "fenced_writes_total"),
        ):
            gauge.bind(lambda attr=attr: getattr(store, attr))
        m.fanout_chunk_size.bind(
            lambda: store.fanout_chunk_events / max(store.fanout_chunks, 1)
        )
        # watch fan-out health (watch_stats takes the watchers' locks:
        # on the reader's thread, not the loop's); its keys, and
        # serving_stats' below, are the series' names less the prefix
        for gauge in (m.watch_queue_depth, m.watch_coalesced_total,
                      m.watch_expired_total):
            key = gauge.name[len("scheduler_"):]
            gauge.bind(lambda key=key: store.watch_stats()[key])
        m.watch_terminated_total.bind(  # by kind
            lambda: dict(store.terminated_by_kind)
        )

        # serving plane: None (no reading) until a replica set is there
        def serving(key: str) -> Optional[float]:
            plane = self._serving_plane()
            return None if plane is None else plane.serving_stats()[key]

        for gauge in (m.apf_seats_current, m.apf_rejected_total,
                      m.server_watch_write_stalls_total,
                      m.replica_failovers_total):
            key = gauge.name[len("scheduler_"):]
            gauge.bind(lambda key=key: serving(key))

    def _serving_plane(self):
        """The APIServerReplicaSet serving this store, or None: it
        announces itself on the store by a weakref (possibly after this
        scheduler was built) and its lifetime is its builder's."""
        ref = getattr(self.store, "serving_plane", None)
        return ref() if ref is not None else None

    # -- event wiring (eventhandlers.go:287) ------------------------------

    def _wire_handlers(self) -> None:
        self.informers.informer("Node").add_handler(self._on_node)
        self.informers.informer("Pod").add_handler(self._on_pod)
        self.informers.informer("Node").add_handler(self.volumes.on_node)
        self.informers.informer("Pod").add_handler(self.volumes.on_pod)
        for kind, handler in (
            ("PersistentVolume", self.volumes.on_pv),
            ("PersistentVolumeClaim", self.volumes.on_pvc),
            ("StorageClass", self.volumes.on_class),
            ("ResourceClaim", self.devices.on_claim),
            ("DeviceClass", self.devices.on_class),
        ):
            inf = self.informers.informer(kind)
            inf.add_handler(handler)
            inf.add_handler(self._on_volume_event)

    def _on_volume_event(self, typ: str, obj, old) -> None:
        # a PV/PVC/StorageClass change can lift a volume-topology static
        # failure (the selector the transform folded in) or free attach
        # capacity — wake statically-parked and resource-parked pods
        self.queue.move_for_event("NodeUpdate")

    def _on_node(self, typ: str, node: api.Node, old) -> None:
        if typ == st.ADDED:
            self.cache.add_node(node)
            self.queue.move_for_event("NodeAdd")
        elif typ == st.MODIFIED:
            self.cache.update_node(node)
            self.queue.move_for_event("NodeUpdate")
        elif typ == st.DELETED:
            self.cache.remove_node(node.meta.name)

    def _on_pod(self, typ: str, pod: api.Pod, old) -> None:
        assigned = bool(pod.spec.node_name)
        if pod.spec.resource_claims and typ != st.DELETED:
            self.devices.track_pod(typ, pod)
        if typ == st.DELETED:
            if assigned:
                # the cache removal must see the claim state the pod was
                # ACCOUNTED under — deallocating first would make
                # remove_pod subtract device counts that were never
                # added (unaccounting symmetry)
                # one interval a removed pod, its wait for cache.lock
                # (an encode or a commit holds it) included
                t0 = _trace.now()
                self.cache.remove_pod(pod)
                _trace.tally("sched.cache.remove", t0, _trace.now())
                # a terminated pod frees resources: unschedulable pods
                # may fit now — but only resource/port/spread/interpod
                # failures can benefit (AssignedPodDelete wake set)
                self.queue.move_for_event("AssignedPodDelete")
            else:
                self.queue.delete(pod)
                self.cache.remove_nomination(pod)
            if pod.spec.resource_claims:
                self.devices.track_pod(typ, pod)
                pkey = pod_key(pod)
                for claim_name in pod.spec.resource_claims:
                    # last consumer gone -> deallocate; dead CARRIER with
                    # sharers -> hand accounting to a survivor — AFTER
                    # unaccounting (dynamicresources.go:275 semantics)
                    self.devices.on_consumer_delete(
                        f"{pod.meta.namespace}/{claim_name}",
                        pkey,
                        cache=self.cache,
                    )
            return
        if assigned:
            # bound (or our own bind echoing back): confirm in cache
            if old is not None and not old.spec.node_name:
                self.queue.done(pod)
            if (
                typ == st.MODIFIED
                and old is not None
                and old.spec.node_name == pod.spec.node_name
            ):
                # already-bound pod changed (in-place resize, label edit):
                # re-account so requested rows track the new spec
                self.cache.update_pod(old, pod)
                self.queue.move_for_event("AssignedPodUpdate")
            else:
                self.cache.add_pod(pod)
                # a newly bound pod can satisfy waiting affinity/spread
                # constraints (AssignedPodAdd cluster event)
                self.queue.move_for_event("AssignedPodAdd")
            return
        if self.profiles.for_pod(pod) is None:
            return  # another scheduler's pod (skipPodSchedule)
        fwk = self.profiles.for_pod(pod)
        reason = fwk.run_pre_enqueue(pod)
        if reason:
            # PreEnqueue rejection: stay out of the queue until the next
            # pod UPDATE re-runs the gate (schedulinggates semantics)
            self.queue.delete(pod)
            return
        if typ == st.ADDED:
            self.queue.add(pod)
        else:
            self.queue.update(pod)

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        """Start informers + the scheduling loop thread."""
        logging.getLogger(__name__).info(
            "scheduler starting: solves run on %s", device_label()
        )
        self.informers.informer("Node").start()
        self.informers.informer("Pod").start()
        self.informers.informer("PersistentVolume").start()
        self.informers.informer("PersistentVolumeClaim").start()
        self.informers.informer("StorageClass").start()
        self.informers.informer("ResourceClaim").start()
        self.informers.informer("DeviceClass").start()
        self.informers.wait_for_sync()
        self._thread = threading.Thread(
            target=self._run, args=(0,), name="scheduler", daemon=True
        )
        self._thread.start()
        # additional profile lanes (multi-profile configs): each pops
        # and solves its own pod classes concurrently
        self._lane_threads = [
            threading.Thread(
                target=self._run, args=(i,), name=f"scheduler-lane{i}",
                daemon=True,
            )
            for i in range(1, len(self._lane_profiles))
        ]
        for t in self._lane_threads:
            t.start()

    def stop(self) -> None:
        self._stop.set()
        self.queue.close()
        if self._thread:
            # a device solve mid-compile can run tens of seconds; tearing
            # the interpreter down under an XLA compile aborts the process,
            # so wait the compile out
            self._thread.join(timeout=120)
        for t in self._lane_threads:
            t.join(timeout=120)
        # drain the binding stage: staged placements are assumed in the
        # cache, so dropping their waves would leak phantom usage until
        # the assume TTL fires
        self.flush_binds(timeout=30)
        with self._wave_cv:
            self._binder_stop = True
            self._wave_cv.notify_all()
        self._bind_thread.join(timeout=10)
        if self._commit_pool is not None:
            self._commit_pool.shutdown(wait=True)
        self.informers.stop()
        self.events.stop()

    def kill(self) -> None:
        """Ungraceful teardown — the chaos harness's process-death
        analogue.  Nothing drains: staged bind waves are dropped on the
        floor and assumed pods are abandoned, exactly what a SIGKILL'd
        scheduler leaves behind (the successor's reconciliation and the
        store's durable state are what recover them).  Never use outside
        crash-restart tests; stop() is the graceful path."""
        # a SIGKILL takes the in-memory obligation ledger with it: the
        # popped/assumed state this instance held is recovered by TTL
        # expiry and successor reconciliation, not discharged
        _ledger.abandon()
        self._stop.set()
        self.queue.close()
        with self._wave_cv:
            self._binder_stop = True
            self._waves.clear()
            self._wave_cv.notify_all()
        if self._thread:
            self._thread.join(timeout=10)
        for t in self._lane_threads:
            t.join(timeout=10)
        self._bind_thread.join(timeout=5)
        if self._commit_pool is not None:
            self._commit_pool.shutdown(wait=False)
        self.informers.stop()
        self.events.stop()

    # -- leadership / restart reconciliation -------------------------------

    def _reconcile_leadership(self) -> None:
        """Make local pipeline state agree with the STORE before the
        first post-acquisition dispatch (on_started_leading's analogue
        of the reference's WaitForCacheSync + queue flush).  A new
        leader — fresh process after a crash, or a warm standby taking
        over — must not trust caches built under someone else's
        leadership:

          * every assumed entry is checked against the store: a pod the
            predecessor (or this process, pre-crash) assumed but never
            durably committed is forgotten and re-queued; a pod the
            store says landed elsewhere is forgotten (the informer
            re-accounts it); a matching bind is kept for the informer to
            confirm;
          * unbound pods missing from the queue entirely (an informer
            gap across the handoff) are swept from the store into it —
            the no-pod-lost floor does not depend on event delivery
            across a leadership boundary;
          * the device mirror is invalidated (next solve performs a full
            RESHARDED re-upload — the delta protocol's resident copy
            belongs to the predecessor's generation history) and the
            solve breaker resets to closed (the cooldown belonged to the
            predecessor's device, not ours).

        Bound-exactly-once across the boundary needs no work here: the
        store is the source of truth, bound pods arrive through the
        informer as bound (never queued), and the wave mutator + write
        fencing reject any late commit that disagrees."""
        log = logging.getLogger(__name__)
        requeued = 0
        try:
            pods, _ = self.store.list("Pod")
        except Exception:  # noqa: BLE001 — retry next cycle
            log.exception("leadership reconcile: store list failed")
            self._reconcile_needed.set()
            return
        by_key = {pod_key(p): p for p in pods}
        for key, node in self.cache.assumed_nodes().items():
            cur = by_key.get(key)
            if cur is not None and cur.spec.node_name == node:
                continue  # durably bound where assumed; informer confirms
            self.cache.forget_key(key, node)
            if cur is not None and not cur.spec.node_name:
                # assumed but never committed: give it back to the queue
                self.queue.add(cur)
                requeued += 1
        # store sweep: unbound pods the queue does not know (popped by a
        # crashed predecessor, or an event lost across the handoff)
        for key, pod in by_key.items():
            if pod.spec.node_name or self.profiles.for_pod(pod) is None:
                continue
            if self.cache.is_assumed(pod):
                continue
            if not self.queue.contains(key):
                self.queue.add(pod)
                requeued += 1
        # device-side state: full mirror re-upload + breaker to closed
        for fwk in self.profiles:
            tpu = fwk.tpu
            mirror = getattr(tpu, "_mirror", None)
            partials = getattr(tpu, "_partials", None)
            if mirror is not None:
                with self.cache.lock:
                    mirror.invalidate()
                    if partials is not None:
                        # the resident partials belong to the same
                        # generation history as the mirror: a new leader
                        # recomputes them whole (warm failover must not
                        # inherit a predecessor's warm rows)
                        partials.invalidate()
            breaker = getattr(tpu, "breaker", None)
            if breaker is not None:
                breaker.reset()
        # the predecessor's Events are this leader's to expire now: its
        # recorder enters what the store holds (by reference, no copy)
        self.events.resync()
        self.metrics.leader_reconcile_total.inc()
        if requeued:
            log.info(
                "leadership reconcile: re-queued %d uncommitted pod(s)",
                requeued,
            )

    # -- binding stage (the dedicated bind worker) -------------------------

    # a wave that failed this many whole-wave commits splits into per-pod
    # commits (the poison-wave escape hatch): one retry, then isolation
    _MAX_WAVE_ATTEMPTS = 1

    def _bind_worker(self) -> None:
        while True:
            with self._wave_cv:
                while not self._waves and not self._binder_stop:
                    self._wave_cv.wait(0.2)
                if not self._waves:
                    return  # stopping and drained
                entries, attempts = self._waves.popleft()
                self._wave_active = True
                self._wave_cv.notify_all()
            # entries not yet committed or failed: the crash handler
            # requeues exactly this remainder, so a crash-grade fault at
            # ANY point (first commit, retry bookkeeping, mid-split)
            # loses nothing to the assume-TTL
            remaining = list(entries)
            try:
                try:
                    self._commit_wave(entries)
                    remaining = []
                except Exception:  # noqa: BLE001 — wave containment
                    # a whole-wave fault must not kill the binding stage
                    # for the process's lifetime NOR park its pods on
                    # the assume-TTL: retry the wave once, then treat it
                    # as poison and split to per-pod commits with
                    # bounded per-pod failure handling
                    if attempts < self._MAX_WAVE_ATTEMPTS:
                        logging.getLogger(__name__).exception(
                            "bind wave failed (attempt %d); retrying",
                            attempts,
                        )
                        with self._wave_cv:
                            self._waves.appendleft((entries, attempts + 1))
                            self._wave_cv.notify_all()
                        remaining = []
                    else:
                        logging.getLogger(__name__).exception(
                            "bind wave failed twice; splitting poison "
                            "wave into per-pod commits"
                        )
                        self.metrics.binder_poison_waves.inc()
                        while remaining:
                            entry = remaining[0]
                            try:
                                self._commit_wave([entry])
                            except Exception:  # noqa: BLE001 — per-pod
                                logging.getLogger(__name__).exception(
                                    "per-pod commit failed for %s; "
                                    "requeueing", pod_key(entry[1].pod),
                                )
                                self._fail_bind(entry[0], entry[1])
                            remaining.pop(0)
            except BaseException:
                # injected crash / interpreter-level fault: the worker
                # is about to die — put the unprocessed remainder back
                # for the restarted worker (_ensure_binder)
                with self._wave_cv:
                    if remaining:
                        self._waves.appendleft((remaining, attempts + 1))
                    self._wave_active = False
                    self._wave_cv.notify_all()
                raise
            with self._wave_cv:
                self._wave_active = False
                self._wave_cv.notify_all()

    def _ensure_binder(self) -> None:
        """Binder watchdog: restart the binding worker if it died (a
        crash-grade fault escaped containment).  Called from the hot
        loop, the wave dispatch path and flush_binds, so direct
        schedule_batch() callers recover too."""
        # double-checked locking: the hot loop calls this every cycle and
        # the worker is almost always alive — the lock-free probe is the
        # fast path; the locked re-check below is authoritative
        if self._bind_thread.is_alive() or self._binder_stop:  # graftlint: disable=guarded-by
            return
        with self._wave_cv:
            if self._bind_thread.is_alive() or self._binder_stop:
                return
            # the dead worker can't clear its active flag; a stale True
            # would wedge flush_binds forever
            self._wave_active = False
            self.metrics.binder_restarts.inc()
            logging.getLogger(__name__).error(
                "binding worker died; restarting (binder supervision)"
            )
            self._bind_thread = threading.Thread(
                target=self._bind_worker, name="bind-wave", daemon=True
            )
            self._bind_thread.start()
            self._wave_cv.notify_all()

    def _dispatch_wave_async(self, wave: List[tuple]) -> None:
        """Hand a bind wave to the binding stage; blocks only when the
        bounded backlog is full (commit slower than solve — the
        backpressure that keeps requeue latency bounded)."""
        self._ensure_binder()
        with self._wave_cv:
            while len(self._waves) >= self._max_wave_backlog:
                self._wave_cv.wait(0.2)
                if not self._bind_thread.is_alive():
                    break  # watchdog's restart will drain the backlog
            self._waves.append((wave, 0))
            self._wave_cv.notify_all()
        self._ensure_binder()

    def flush_binds(self, timeout: float = 30.0) -> bool:
        """Block until every dispatched bind wave has committed (tests
        and shutdown; the hot path never waits).  True on drained."""
        deadline = time.monotonic() + timeout
        while True:
            self._ensure_binder()
            with self._wave_cv:
                # predicate loop under ONE acquisition (graftlint
                # atomicity cv-discipline); breaks out to re-run the
                # binder watchdog when the worker died mid-drain — a
                # dead worker can never notify this cv again
                while (
                    self._waves or self._wave_active or self._stream_inflight
                ):
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        return False
                    self._wave_cv.wait(min(remaining, 0.2))
                    if not self._bind_thread.is_alive():
                        break
                else:
                    return True

    # -- per-thread in-flight cycle tracking ------------------------------

    def _inflight_set(self, cycle: Optional["_Cycle"]) -> None:
        ident = threading.get_ident()
        with self._inflight_lock:
            if cycle is None:
                self._inflight_cycles.pop(ident, None)
            else:
                self._inflight_cycles[ident] = cycle

    def _inflight_get(self) -> Optional["_Cycle"]:
        with self._inflight_lock:
            return self._inflight_cycles.get(threading.get_ident())

    # -- speculative solve overlap ----------------------------------------

    def _spec_token(self) -> int:
        """The wave-failure generation a speculative dispatch records;
        any commit failure / fence bumps it (see _note_commit_failure)."""
        with self._spec_lock:
            return self._wave_fail_gen

    def _spec_invalidated(self, token: int) -> bool:
        with self._spec_lock:
            return self._wave_fail_gen != token

    def _note_commit_failure(self) -> None:
        """A staged placement was released on the commit side (failed
        sub-wave, fenced wave, PreBind error): any batch dispatched
        speculatively over the released assumes must invalidate."""
        with self._spec_lock:
            self._wave_fail_gen += 1

    def _waves_in_flight(self) -> bool:
        with self._wave_cv:
            return bool(
                self._waves or self._wave_active or self._stream_inflight
            )

    # -- streamed sub-wave commits ----------------------------------------

    def _dispatch_subwave_async(self, entries: List[tuple], sid: int) -> None:
        """Hand one store shard's staged slice of a wave to the commit
        pool immediately (before the rest of the wave stages).  Bounded
        by 2x the pool width so a slow store backpressures the solve
        stage instead of growing an unbounded in-flight set."""
        faults.fire("binder.stream_subwave", pods=len(entries), shard=sid)
        cap = 2 * self._subwave_width
        with self._wave_cv:
            while self._stream_inflight >= cap and not self._binder_stop:
                self._wave_cv.wait(0.2)
            self._stream_inflight += 1
            _ledger.push("stream_inflight", id(self))
            self._wave_cv.notify_all()
        try:
            self._commit_pool.submit(self._commit_stream_subwave, entries)
        except BaseException:
            with self._wave_cv:
                self._stream_inflight -= 1
                _ledger.pop("stream_inflight", id(self))
                self._wave_cv.notify_all()
            raise

    def _commit_stream_subwave(self, entries: List[tuple]) -> None:
        """One streamed per-shard sub-wave on the commit pool.  The
        wave-retry/poison machinery stays with the whole-wave binder
        path; a streamed sub-wave commits once and a whole-sub-wave
        fault requeues its pods with backoff (bound-exactly-once per
        sub-wave holds: the mutator's already-bound guard plus fencing
        reject any duplicate commit)."""
        try:
            self._commit_wave(entries)
        except BaseException:  # noqa: BLE001 — crash-grade containment:
            # the pool thread must survive and the pods must not strand
            # on the assume TTL
            logging.getLogger(__name__).exception(
                "streamed sub-wave commit failed; requeueing %d pod(s)",
                len(entries),
            )
            for fwk, info, _, _ in entries:
                try:
                    self._fail_bind(fwk, info)
                except Exception:  # noqa: BLE001
                    logging.getLogger(__name__).exception(
                        "streamed sub-wave requeue failed for %s",
                        pod_key(info.pod),
                    )
        finally:
            with self._wave_cv:
                self._stream_inflight -= 1
                _ledger.pop("stream_inflight", id(self))
                self._wave_cv.notify_all()

    def _solve_window(self, start: float, end: float) -> None:
        with self._solve_lock:
            self._solve_windows.append((start, end))
            self._solve_open = None

    def _solve_overlap(self, t0: float, t1: float) -> float:
        """Seconds of [t0, t1] that intersected device-solve windows —
        the realized pipeline overlap for one wave commit."""
        with self._solve_lock:
            spans = list(self._solve_windows)
            if self._solve_open is not None:
                spans.append((self._solve_open, t1))
        total = 0.0
        for s, e in spans:
            total += max(0.0, min(e, t1) - max(s, t0))
        return min(total, max(t1 - t0, 0.0))

    def _commit_wave(self, wave: List[tuple]) -> None:
        """Commit one bind wave: PreBind per pod, then ONE store
        transaction for every surviving bind, then the per-pod success
        tail.  Failures split per pod back to individual requeue — a bad
        pod never takes its wave down."""
        faults.fire("binder.commit_wave", pods=len(wave))
        # the wave belongs to the cycle that staged it: this worker's
        # span names that cycle, and its two clock reads are also the
        # wave's duration below (no second pair of reads)
        cyc = wave[0][1].trace_cycle if wave else 0
        with _trace.span("sched.commit", len(wave), cycle=cyc, parent=cyc) as sp:
            for _, info, _, _ in wave:
                _trace.stamp(info.trace_slot, _trace.COMMIT_BEGIN, sp.t0)
            self._commit_wave_spanned(wave)
        dt = sp.t1 - sp.t0
        self.metrics.commit_wave_duration.observe(dt)
        self.metrics.commit_wave_size.observe(float(len(wave)))
        if self.window_ctl is not None:
            self.window_ctl.note_commit(len(wave), dt)
        self.metrics.pipeline_overlap.observe(
            self._solve_overlap(sp.t0, sp.t1)
        )

    def _commit_wave_spanned(self, wave: List[tuple]) -> None:
        binds: List[tuple] = []
        with _trace.span("sched.commit.pre_bind", len(wave)):
            for fwk, info, node_name, t_attempt in wave:
                try:
                    fwk.run_pre_bind(info.pod, node_name)
                except Exception:  # noqa: BLE001 — per-pod containment
                    self._fail_bind(fwk, info)
                    continue
                binds.append((fwk, info, node_name, t_attempt))
        if not binds:
            return

        def bind_mutator(node_name: str):
            def mutate(pod: api.Pod) -> None:
                if pod.spec.node_name and pod.spec.node_name != node_name:
                    # bound-exactly-once guard: a retried wave must
                    # never move an already-bound pod (same-node
                    # recommit is an idempotent no-op-shaped write)
                    raise st.Conflict(
                        f"pod already bound to {pod.spec.node_name}"
                    )
                pod.spec.node_name = node_name
                pod.status.phase = "Running"
            return mutate

        # stale-leader write fencing: every sub-wave commits only
        # while our lease acquisition is still current (a deposed
        # leader's late sub-wave is rejected inside its transaction
        # — the Fenced path below requeues; the pods belong to the
        # successor now)
        fence = None
        if self.leader_elector is not None:
            token = getattr(self.leader_elector, "fence_token", None)
            if token is not None:
                fence = token()
        failed = self._commit_subwaves(binds, bind_mutator, fence)
        with _trace.span("sched.commit.post_bind", len(binds)):
            done: List[api.Pod] = []
            for fwk, info, node_name, t_attempt in binds:
                if pod_key(info.pod) in failed:
                    self._fail_bind(fwk, info)
                    continue
                done.append(info.pod)
                self._finish_bound(
                    fwk, info, node_name, t_attempt, finish_binding=False
                )
            # TTL countdown for the whole wave under one lock/clock read
            self.cache.finish_binding_all(done)

    def _commit_subwaves(self, binds, bind_mutator, fence) -> set:
        """Commit one bind wave as per-store-shard SUB-waves — each an
        atomic ``update_wave`` transaction on its shard, committed
        CONCURRENTLY (up to commit_subwave_concurrency) so shard A's
        journal append / watch fan-out overlaps shard B's and the next
        solve.  A 1-shard store (or a wave whose pods all live on one
        shard) keeps the single-transaction path.  Returns the set of
        pod keys that must requeue (per-object errors, a fenced
        sub-wave, or a whole-sub-wave failure)."""
        shard_of = getattr(self.store, "shard_index", None)
        groups: "Dict[int, List[tuple]]" = {}
        for entry in binds:
            sid = (
                shard_of("Pod", entry[1].pod.meta.namespace)
                if shard_of is not None else 0
            )
            groups.setdefault(sid, []).append(entry)

        def commit_group(sid, group):
            updates = [
                (info.pod.meta.name, info.pod.meta.namespace,
                 bind_mutator(node_name))
                for _, info, node_name, _ in group
            ]
            t_g = self._clock()
            try:
                # the binder already partitioned by shard_index: the
                # shard hint lets the store skip re-hashing every pod
                # (the streamed hand-off fast path)
                kwargs = {"fence": fence}
                if shard_of is not None:
                    kwargs["shard_hint"] = sid
                _, errs = self.store.update_wave("Pod", updates, **kwargs)
                bad = set(errs)
                # the binds are in the store, and in the journal as
                # journal_sync promises: one read for the sub-wave
                t_done = _trace.now()
                for _, info, _, _ in group:
                    if not bad or pod_key(info.pod) not in bad:
                        _trace.stamp(info.trace_slot, _trace.COMMITTED, t_done)
            except st.Fenced:
                logging.getLogger(__name__).warning(
                    "bind sub-wave fenced (leadership lost since "
                    "staging); requeueing %d pod(s) for the new leader",
                    len(group),
                )
                bad = {pod_key(info.pod) for _, info, _, _ in group}
            except Exception:  # noqa: BLE001 — sub-wave containment
                logging.getLogger(__name__).exception(
                    "sub-wave transaction failed; requeueing its pods"
                )
                bad = {pod_key(info.pod) for _, info, _, _ in group}
            return bad, self._clock() - t_g

        failed: set = set()
        durations: List[float] = []
        t_all = self._clock()
        if len(groups) > 1 and self._commit_pool is not None:
            futures = [
                self._commit_pool.submit(commit_group, sid, g)
                for sid, g in groups.items()
            ]
            for f in futures:
                bad, dt = f.result()
                failed |= bad
                durations.append(dt)
        else:
            for sid, g in groups.items():
                bad, dt = commit_group(sid, g)
                failed |= bad
                durations.append(dt)
        wall = self._clock() - t_all
        for dt in durations:
            self.metrics.commit_subwave_duration.observe(dt)
        # realized cross-shard commit concurrency: sub-wave work that
        # ran while another sub-wave of this wave was also committing
        self.metrics.commit_subwave_overlap.observe(
            max(sum(durations) - wall, 0.0)
        )
        return failed

    def _fail_bind(self, fwk: Framework, info: QueuedPodInfo) -> None:
        """The binding stage's per-pod failure tail: forget the assume,
        roll back reservations, requeue with backoff.  Also bumps the
        wave-failure generation: a batch dispatched speculatively over
        this (now released) assume invalidates at harvest."""
        self._note_commit_failure()
        released = self.cache.forget(info.pod)
        fwk.run_unreserve(info.pod)
        if released:
            # the assume had accounted real capacity; its release is an
            # AssignedPodDelete-shaped event — without it, pods parked on
            # REASON_RESOURCES would sleep until the flush interval even
            # though the space just came back
            self.queue.move_for_event("AssignedPodDelete")
        self.metrics.schedule_attempts.inc("error")
        self._mark_failed(info, _trace.FAIL_BIND)
        self.queue.requeue_backoff(info)

    def _run(self, lane_idx: int = 0) -> None:
        # Each pass is one whole cycle: pop -> _dispatch_batch ->
        # _finish_cycle, the halves schedule_batch() calls back to back.
        # The lane blocks in the decode for as long as the device and the
        # readback take, stages, hands the wave to the commit workers, and
        # only then goes back to the queue: a solved batch never waits
        # out the next batch's accumulation window.  The window opens
        # when the dispatch returns (`window_end`), so the device's time
        # and the finish are charged to it, not added: the pop takes only
        # what is left, and the cycle's period grows by neither.
        #
        # Each profile LANE runs this loop over its own disjoint pod
        # classes (multi-profile configs); lane 0 is the LEAD lane —
        # leadership reconciliation and the assume-TTL sweep run there
        # only, once per pass, never once per lane.
        lead = lane_idx == 0
        profiles = self._lane_profiles[lane_idx]
        window = min(0.05, self.config.batch_window_seconds or 0.05)
        window_end: Optional[float] = None
        while not self._stop.is_set():
            # only the pass right after a dispatch pops against its window
            pop_by, window_end = window_end, None
            self._ensure_binder()
            if self.leader_elector and not self.leader_elector.is_leader():
                time.sleep(0.05)
                continue
            if self._reconcile_needed.is_set():
                if not lead:
                    # reconciliation is in flight on the lead lane: a
                    # follower lane must not dispatch over un-reconciled
                    # caches — wait for the lead to clear the flag
                    time.sleep(0.01)
                    continue
                # first pass after start or (re)acquired leadership:
                # reconcile local state against the store BEFORE popping
                self._reconcile_needed.clear()
                try:
                    self._reconcile_leadership()
                except Exception:  # noqa: BLE001 — containment
                    logging.getLogger(__name__).exception(
                        "leadership reconcile failed; continuing"
                    )
            try:
                # bounded by what is left of the window, so the adaptive
                # controller's wider answers (up to 0.25 s) never stretch
                # a busy lane's period; an idle lane polls at 0.2 s
                timeout = 0.2 if pop_by is None else max(
                    0.0, pop_by - self._clock()
                )
                batch, t_pop = self._pop(timeout, profiles)
            except Exception:  # noqa: BLE001
                batch = []
            if (
                batch
                and self.leader_elector
                and not self.leader_elector.is_leader()
            ):
                # leadership was lost INSIDE the pop window: a
                # stepped-down scheduler must not dispatch — hand the
                # batch back and wait for re-acquisition
                for info in batch:
                    self.queue.requeue_backoff(info)
                batch = []
            try:
                if batch:
                    cycle = self._dispatch_batch(batch, t_pop)
                    window_end = self._clock() + window
                    self._finish_cycle(cycle)
                    # a speculative cycle bookmarks the mirror's device
                    # tensors: keep them no longer than the cycle itself
                    del cycle
            except Exception:  # noqa: BLE001 — per-cycle containment
                # the reference contains per-cycle errors (ScheduleOne
                # logs and returns; the wait.Until loop re-enters) — one
                # lost race must not kill the scheduling thread for the
                # process's lifetime.  Salvage first: popped pods the
                # dead cycle never dispositioned go back to the queue
                # instead of stranding in the 'inflight' tier.
                self._salvage_cycle(self._inflight_get())
                logging.getLogger(__name__).exception(
                    "schedule_batch cycle failed; continuing"
                )
            if lead:
                for pod in self.cache.cleanup_expired():
                    # binding never confirmed: give the pod another chance
                    self.queue.add(pod)

    def _salvage_cycle(self, cycle: Optional["_Cycle"]) -> None:
        """A cycle died mid-flight: dispatch whatever bind-wave entries
        it had fully staged (assumed + Permit-allowed — safe to commit),
        then requeue every popped pod no terminal path owned, forgetting
        any assume the dead cycle left behind.  The chaos invariant this
        maintains: every popped pod ends bound or back in the queue,
        never wedged inflight."""
        self._inflight_set(None)
        if cycle is None:
            return
        cycle.trace.close()
        if cycle.wave:
            staged, cycle.wave = cycle.wave, []
            for _, info, _, _ in staged:
                cycle.handled.add(pod_key(info.pod))
            try:
                self._dispatch_wave_async(staged)
            except Exception:  # noqa: BLE001
                logging.getLogger(__name__).exception(
                    "salvage: staged wave dispatch failed; requeueing"
                )
                for fwk, info, _, _ in staged:
                    self._fail_bind(fwk, info)
        for info in cycle.batch:
            key = pod_key(info.pod)
            if key in cycle.handled:
                continue
            cycle.handled.add(key)
            if self.cache.is_assumed(info.pod):
                # the dead cycle assumed it but lost it before staging
                self.cache.forget(info.pod)
            self.metrics.schedule_attempts.inc("error")
            self._mark_failed(info, _trace.FAIL_SALVAGED)
            self.queue.requeue_backoff(info)

    # -- the batched scheduling cycle -------------------------------------

    def schedule_batch(self, timeout: Optional[float] = None) -> Dict[str, int]:
        """One synchronous solve-stage cycle: drain -> device solve ->
        assume each placement -> hand the bind wave to the binding stage
        -> park failures.  Returns counters for tests/metrics.

        `scheduled` counts pods staged into the bind wave (assumed, past
        Permit): the wave commits asynchronously, and a bind error later
        splits that pod back to requeue (metrics record it as an error).
        Callers that need the binds durable call flush_binds().

        The hot loop (_run) runs the same _dispatch_batch/_finish_cycle
        halves, back to back as here: strict pop->solve->stage."""
        batch, t_pop = self._pop(timeout)
        if not batch:
            return {"popped": 0, "scheduled": 0, "unschedulable": 0,
                    "bind_errors": 0}
        try:
            return self._finish_cycle(self._dispatch_batch(batch, t_pop))
        except Exception:
            # direct callers see the error, but popped pods must not
            # strand inflight (the same salvage the hot loop runs)
            self._salvage_cycle(self._inflight_get())
            raise

    def _pop(self, timeout, profiles=None) -> tuple:
        """``pop_batch`` inside a ``sched.pop_wait`` span of no cycle:
        its end is the start of the cycle the batch runs as, by the same
        clock read, which is all that joins them.  Returns (batch, that
        read)."""
        with _trace.span("sched.pop_wait", cycle=0, parent=0) as sp:
            batch = self.queue.pop_batch(
                self.batch_size, timeout=timeout, profiles=profiles
            )
            sp.n, sp.a0 = len(batch), self.queue.last_window
        return batch, sp.t1

    def _mark_failed(self, info: QueuedPodInfo, code: int) -> None:
        """The pod's attempt ended without a bind: stamp when and how."""
        _trace.stamp(info.trace_slot, _trace.FAILED)
        _trace.stamp(info.trace_slot, _trace.FAIL_CODE, code)

    def _dispatch_batch(
        self, batch: List[QueuedPodInfo], t_pop: Optional[float] = None
    ) -> "_Cycle":
        """The dispatch half of one cycle: group the popped batch by
        profile, encode + dispatch each group's device solve.  Each group
        runs its FULL cycle (solve -> assume -> bind) before the next
        group solves — assume lands the placements in the shared state,
        so a later profile's snapshot sees them; only the LAST group's
        decode+staging is left pending for _finish_cycle, which every
        caller runs next."""
        stats = {"popped": len(batch), "scheduled": 0, "unschedulable": 0,
                 "bind_errors": 0}
        if not self._speculation_enabled:
            # speculative_solve=false: strict solve-vs-commit
            # serialization — a new batch dispatches only over durably
            # committed waves (the rollback knob; the default pipeline
            # overlaps and invalidates on failure instead)
            self.flush_binds(timeout=30.0)
        # Encode under the cache lock (informer threads mutate the same
        # ClusterState/vocabularies); solve outside it.  A pod whose spec
        # can't be encoded (cap overflow, unsupported field) must only
        # reject that pod, not kill the loop (the reference marks the one
        # pod unschedulable, handleSchedulingFailure).
        reservations = self.cache.nominations_excluding(
            {pod_key(info.pod) for info in batch}
        )
        # slow cycles self-describe on EVERY exit path (utiltrace
        # LogIfLong, schedule_one.go:391-431); threshold is generous
        # because first-shape compiles legitimately run tens of seconds.
        # _finish_cycle's log_if_long is the ONE emission point.  The
        # cycle starts where its pop ended (`t_pop`; now, for a caller
        # that popped for itself), which is every pod's ``popped``.
        tr = _trace.Trace("schedule_batch", threshold=1.0, span="sched.cycle",
                          start=t_pop, pods=len(batch))
        for info in batch:
            slot = info.trace_slot
            info.trace_cycle = tr.id
            _trace.stamp(slot, _trace.POPPED, tr.start)
            _trace.stamp(slot, _trace.CYCLE, tr.id)
            _trace.stamp(slot, _trace.ATTEMPTS, info.attempts)
        cycle = _Cycle(stats, tr, reservations, batch)
        self._inflight_set(cycle)
        if self._speculation_enabled and self._waves_in_flight():
            # SPECULATIVE dispatch: this batch's encode/solve runs over
            # placements an in-flight wave only ASSUMED.  Record the
            # wave-failure generation — a commit failure/fence before
            # this cycle harvests invalidates it (requeue, not stage).
            self.metrics.speculative_solves_total.inc()
            faults.fire("solve.speculate", pods=len(batch))
            cycle.spec_token = self._spec_token()
        # A pod can be popped twice into one accumulation window (delete
        # + recreate races a mid-cycle requeue): the duplicate would make
        # cache.assume raise "already assumed" downstream — requeue it
        # per-pod here instead of letting it near the solve.
        seen: set = set()
        deduped: List[QueuedPodInfo] = []
        for info in batch:
            key = pod_key(info.pod)
            if key in seen:
                cycle.handled.add(key)
                self.metrics.schedule_attempts.inc("error")
                self.queue.requeue_backoff(info)
                continue
            seen.add(key)
            deduped.append(info)
        batch = deduped
        by_fwk: Dict[str, List[QueuedPodInfo]] = {}
        for info in batch:
            by_fwk.setdefault(info.pod.spec.scheduler_name, []).append(info)
        groups = [
            (name, group, self.profiles.frameworks.get(name))
            for name, group in by_fwk.items()
        ]
        # another scheduler's pod slipped in.  Normally unreachable (the
        # informer and the reconcile sweep both filter on profile), but a
        # popped pod is an obligation: dropping the group silently would
        # strand its members on the inflight tier forever.  Retire each
        # with an explicit disposition instead.
        for name, group, fwk in groups:
            if fwk is not None:
                continue
            for info in group:
                key = pod_key(info.pod)
                cycle.handled.add(key)
                self.metrics.schedule_attempts.inc("error")
                self.queue.done(info.pod)
                self.events.eventf(
                    info.pod, "Warning", "FailedScheduling",
                    f"no framework profile for scheduler {name!r}",
                )
        groups = [g for g in groups if g[2] is not None]
        for idx, (sched_name, group, fwk) in enumerate(groups):
            solved = self._solve_group_async(cycle, fwk, sched_name, group)
            if solved is None:
                continue
            cycle.solved_any = True
            if idx == len(groups) - 1:
                cycle.pending = solved
            else:
                self._harvest_group(cycle, *solved)
        return cycle

    def _solve_group_async(self, cycle, fwk, sched_name, group):
        """Encode + dispatch one profile group; returns (fwk, name,
        group, DeviceSolve, t_solve) or None when nothing solvable."""
        t_solve = _trace.now()
        with self._solve_lock:
            self._solve_open = t_solve
        if cycle.spec_token is not None:
            # speculative encode: bookmark the profile's device-mirror
            # resident buffer (the double-buffer base) so invalidation
            # can drop the speculative delta chain whole
            mirror = getattr(fwk.tpu, "_mirror", None)
            partials = getattr(fwk.tpu, "_partials", None)
            if mirror is not None and sched_name not in cycle.mirror_points:
                with self.cache.lock:
                    cycle.mirror_points[sched_name] = (
                        mirror, mirror.speculation_point()
                    )
                    if partials is not None:
                        # the resident partials double-buffer with the
                        # mirror: one bookmark pair, taken atomically
                        cycle.partials_points[sched_name] = (
                            partials, partials.speculation_point()
                        )
        pods = [info.pod for info in group]
        try:
            ds = fwk.tpu.schedule_pending_async(
                pods, lock=self.cache.lock, reservations=cycle.reservations
            )
        except (OverflowError, ValueError):
            group = self._reject_unencodable(group, fwk, cycle)
            if not group:
                with self._solve_lock:
                    self._solve_open = None
                return None
            try:
                ds = fwk.tpu.schedule_pending_async(
                    [info.pod for info in group], lock=self.cache.lock,
                    reservations=cycle.reservations,
                )
            except (OverflowError, ValueError):
                # cumulative/batch-level encode failure even though
                # each pod encodes alone: park the whole group rather
                # than killing the scheduler thread
                with self._solve_lock:
                    self._solve_open = None
                for info in group:
                    cycle.handled.add(pod_key(info.pod))
                    self.metrics.schedule_attempts.inc("error")
                    self.queue.add_unschedulable(
                        info, reason=assign_ops.REASON_UNENCODABLE
                    )
                return None
        return (fwk, sched_name, group, ds, t_solve)

    def _misspeculate_group(self, cycle, fwk, sched_name, group, ds) -> None:
        """A wave this group's solve speculated over failed or was
        fenced after the dispatch: the solve ran against assumed
        placements that no longer hold.  Discard the solve undecoded
        (releasing its dispatch slot), roll the profile's mirror back to
        its pre-speculation resident buffer, and requeue EXACTLY this
        batch with backoff — bounded, because attempts already counted
        at pop and backoff grows per retry."""
        if hasattr(ds, "release_slot"):
            ds.release_slot()
        point = cycle.mirror_points.get(sched_name)
        if point is not None:
            mirror, bookmark = point
            with self.cache.lock:
                mirror.rollback(bookmark)
                ppoint = cycle.partials_points.get(sched_name)
                if ppoint is not None:
                    # partials roll back WITH the mirror: warm rows must
                    # never outlive the resident tensors they were
                    # evaluated against (partials_rollbacks_total)
                    partials, pbookmark = ppoint
                    partials.rollback(pbookmark)
        self.metrics.misspeculation_total.inc()
        logging.getLogger(__name__).info(
            "mis-speculation: requeueing %d pod(s) of profile %s "
            "(a wave failed/fenced after the speculative dispatch)",
            len(group), sched_name,
        )
        for info in group:
            t_fail = _trace.now()
            cycle.handled.add(pod_key(info.pod))
            self._mark_failed(info, _trace.FAIL_MISSPECULATED)
            self.queue.requeue_backoff(info)
            cycle.note_failure(t_fail)

    def _harvest_group(self, cycle, fwk, sched_name, group, ds, t_solve):
        """Decode one dispatched group (the coalesced readback) and stage
        its placements."""
        if cycle.spec_token is not None and self._spec_invalidated(
            cycle.spec_token
        ):
            self._misspeculate_group(cycle, fwk, sched_name, group, ds)
            return
        names = fwk.tpu.finalize_pending(
            [info.pod for info in group], ds, lock=self.cache.lock,
            reservations=cycle.reservations,
        )
        # the breaker's retry/fallback may have replaced the solve the
        # names came from — read telemetry off the effective one, never
        # the sick original (its decode raises)
        ds = getattr(fwk.tpu, "last_solve", None) or ds
        # one read for the group: its solve has returned names
        now = _trace.now()
        route = _trace.ROUTE_ID.get(
            getattr(getattr(ds, "meta", None), "route", None),
            _trace.ROUTE_ID["host"],
        )
        for info in group:
            _trace.stamp(info.trace_slot, _trace.SOLVED, now)
            _trace.stamp(info.trace_slot, _trace.ROUTE, route)
        lt = fwk.tpu.last_timings or {}
        encode_s = float(lt.get("encode_s", 0.0))
        compile_s = float(lt.get("compile_s", 0.0))
        decode_wait = float(lt.get("decode_wait_s", 0.0))
        overlap_s = float(lt.get("decode_overlap_s", 0.0))
        # overlap window = the DEVICE half only: the encode holds the
        # cache lock, which a concurrent wave commit also needs, so only
        # the device dispatch truly pipelines against commits
        self._solve_window(
            min(t_solve + encode_s + compile_s, now), now
        )
        # one device dispatch solved len(group) pods.  batch_solve
        # observes the EXPOSED solve cost — encode + compile + the decode
        # wait the host actually blocked on (in the loop: the device's
        # whole solve and the readback); what a caller hid behind work of
        # its own before decoding shows up in decode_overlap instead.  The
        # reference-named per-pod algorithm metric gets the per-pod share
        # so harness percentiles stay comparable with the reference's
        # per-ScheduleOne numbers.
        dt_exposed = encode_s + compile_s + decode_wait
        if self.window_ctl is not None:
            # compile walls are one-off; the steady per-pod solve cost
            # the window should size against excludes them
            self.window_ctl.note_solve(
                len(group), encode_s + decode_wait
            )
        self.metrics.batch_solve_duration.observe(dt_exposed)
        self.metrics.scheduling_algorithm_duration.observe(
            dt_exposed / max(len(group), 1), count=len(group)
        )
        self.metrics.decode_overlap.observe(overlap_s)
        if compile_s > 0.01:
            # a real trace/compile, not dispatch-enqueue noise
            self.metrics.solve_compile_duration.observe(compile_s)
        if ds.wave_count is not None:
            # read back with the names, in the one coalesced device_get
            self.metrics.solve_wave_count.observe(float(ds.wave_count))
            self.metrics.solve_wave_fallbacks.observe(
                float(ds.wave_fallbacks or 0)
            )
            self.metrics.solve_wave_steps.observe(float(ds.wave_steps or 0))
            _trace.event(
                "sched.solve.waves", now, now, ds.wave_count,
                a0=float(ds.wave_fallbacks or 0),
                a1=float(ds.wave_steps or 0),
            )
        if ds.frag_score is not None:
            # slice-family solve: mirror the carve-out telemetry (same
            # coalesced readback as the names — no extra round-trip)
            self.metrics.fragmentation_score.set(float(ds.frag_score))
            self.metrics.slice_carveouts.inc(by=float(ds.carveouts or 0))
            self.metrics.gang_contiguous_placements.inc(
                by=float(ds.contiguous_gangs or 0)
            )
            self.metrics.slice_carveout_fallbacks.inc(
                by=float(ds.carveout_fallbacks or 0)
            )
        # reasons come from the SAME readback as the names; after a gang
        # admission retry the solve result no longer aligns positionally
        # (unplaced pods there are unadmitted gang members — REASON_GANG
        # by construction) and last_result reflects that
        result = fwk.tpu.last_result
        if result is ds.result and ds.reasons() is not None:
            reasons = ds.reasons()
        elif result is not None and result.reasons is not None:
            reasons = [
                int(r) for r in np.asarray(result.reasons)[: len(group)]
            ]
        else:
            reasons = [-1] * len(group)
        with _trace.span("sched.stage", len(group)) as sp:
            # which profile: an attribute, so span names stay a closed set
            sp.a0 = self._profile_ids.get(sched_name, -1)
            self._stage_group(fwk, group, names, reasons, cycle)

    def _finish_cycle(self, cycle: "_Cycle") -> Dict[str, int]:
        """The staging half: decode the cycle's last group, hand the bind
        wave to the binding stage, run PostFilter, emit trace/metrics."""
        if cycle.pending is not None:
            pending, cycle.pending = cycle.pending, None
            self._harvest_group(cycle, *pending)
        stats, tr = cycle.stats, cycle.trace
        if cycle.fail_n:
            # the failure branch, one row a cycle that had one: n pods
            # that ended without a bind, a1 of them parked (the rest
            # went back on the queue at once), a0 the seconds their
            # branches took (mark, park or requeue, FailedScheduling
            # event) between the row's own start and end
            _trace.event("sched.fail", cycle.fail_t0, cycle.fail_t1,
                         cycle.fail_n, a0=cycle.fail_s,
                         a1=cycle.fail_parked)
        if cycle.wave:
            # binding stage takes over: the NEXT cycle's pop+solve runs
            # while this wave commits (assume entries already bridge it)
            with _trace.span("sched.wave_handoff", len(cycle.wave)):
                self._dispatch_wave_async(cycle.wave)
        # did the placement work block on a trace/compile?  (read
        # before the PostFilter pass, which is timed out of the ladder's
        # feed whole, its own compiles included)
        n_compiled = compileclock.events() - cycle.compile_mark
        compiled = n_compiled != 0
        if cycle.solved_any:
            # PostFilter: preemption for unschedulable pods, highest
            # priority first (handleSchedulingFailure ->
            # Evaluator.Preempt, schedule_one.go:1017, preemption.go:150).
            # The whole batch shares ONE victim-tensor encode + device
            # dry-run (PreemptionEvaluator.shared_pass); victim deletes
            # emit AssignedPodDelete events that requeue the nominee.
            # Under overload the batch is CAPPED at level 1 (the batched
            # solve amortized the per-pod marginal cost — preemption
            # load spikes exactly when the cluster is overloaded, so
            # deferring it outright was backwards) and deferred only at
            # level 2; pods past the cap count into overload_shed_total
            # and retry with backoff — the scheduler's load shed them,
            # not the cluster, so no event would wake them, and in a
            # cluster gone idle their retries are the only cycles left
            # to bring the level down.
            cycle.failed.sort(key=lambda i: -i.pod.spec.priority)
            with _trace.span("sched.postfilter", len(cycle.failed)) as sp_post:
                budget = self.max_preemptions_per_cycle
                level = self.overload.level()
                if level >= 2:
                    budget = 0
                elif level == 1:
                    budget = max(1, budget // 4)
                eligible = cycle.failed[: self.max_preemptions_per_cycle]
                batch_infos = eligible[:budget]
                try:
                    if batch_infos:
                        # concurrent lanes serialize their PostFilter passes:
                        # the evaluator's shared pass caches per-pass state
                        # (victim tensors, priority floor) one pass at a time
                        with self._postfilter_lock, self.preemption.shared_pass(
                            [info.pod for info in batch_infos]
                        ):
                            for info in batch_infos:
                                fwk = self.profiles.for_pod(info.pod)
                                if fwk is not None and fwk.run_post_filter(
                                    info.pod
                                ):
                                    stats["preempted"] = (
                                        stats.get("preempted", 0) + 1
                                    )
                except (faults.FaultCrash, Exception):  # noqa: BLE001
                    # preemption is background work: a crash-grade fault in
                    # the batched dry-run must not kill the scheduling
                    # thread — the failed pods stay parked and retry on a
                    # later cycle (the flush interval is the floor)
                    logging.getLogger(__name__).exception(
                        "PostFilter preemption pass failed; continuing"
                    )
                shed = eligible[len(batch_infos):]
                if shed:
                    self.metrics.overload_shed_total.inc(by=float(len(shed)))
                    for info in shed:
                        self.queue.retry_parked(info)
            postfilter_s = sp_post.t1 - sp_post.t0
        else:
            postfilter_s = 0.0
        total = tr.total
        tr.log_if_long()
        self.metrics.schedule_batch_duration.observe(total)
        # overload ladder: feed the cycle's PLACEMENT duration — the
        # PostFilter pass is excluded (see OverloadController: shedding
        # must not be driven by the work it sheds), and a cycle that
        # compiled is no reading of load at all — and let the adaptive
        # window react (level 2 pins it wide)
        if compiled:
            level = self.overload.level()
        else:
            level = self.overload.note_cycle(
                max(total - postfilter_s, 0.0)
            )
        if self.window_ctl is not None:
            self.window_ctl.set_overload(level)
        # serving plane: feed the adaptive APF ladder (overload level +
        # store depths); exception-contained — serving-plane trouble
        # must never take the scheduling loop down with it.
        plane = self._serving_plane()
        if plane is not None:
            try:
                plane.note_scheduler(level, self.store)
            except Exception:  # noqa: BLE001 — containment
                logging.getLogger(__name__).exception(
                    "serving-plane pressure note failed"
                )
        # the root span ends here (operator gauges are not fed from the
        # loop: _bind_gauges)
        tr.close(a0=n_compiled, a1=level)
        self._inflight_set(None)
        return stats

    def _stage_group(
        self,
        fwk: Framework,
        group: List[QueuedPodInfo],
        names: List[Optional[str]],
        reasons: List[int],
        cycle: "_Cycle",
    ) -> None:
        """Assume one profile's placements and stage them into the bind
        wave (the per-pod tail of ScheduleOne, schedule_one.go:118-133
        batched; the bind itself runs on the binding stage).  Permit
        ordering is preserved: reject aborts here, wait parks the pod on
        its own WaitOnPermit thread exactly as before — only the
        allow-path bind moves into the wave.  Every branch marks the pod
        handled so a mid-cycle fault salvages only truly-orphaned pods.

        A duplicate assume ("already assumed" ValueError — the same pod
        reaching the solve twice despite the dispatch dedup) is contained
        to a per-pod requeue-with-backoff; it never kills the cycle.

        STREAMED sub-wave commits (stream_subwaves, multi-shard stores):
        instead of accumulating the whole group into ``cycle.wave`` and
        dispatching after the full readback+staging, the group is staged
        per STORE SHARD and each shard's slice is handed to the commit
        pool the moment it finishes staging — shard A's journal fsync /
        watch fan-out run while shard B's pods are still staging (and
        while the next solve runs).  Each pod lands in exactly ONE
        streamed sub-wave, and every sub-wave carries the same fence /
        bound-exactly-once semantics as a whole wave."""
        shard_of = getattr(self.store, "shard_index", None)
        if not (self._stream_enabled and shard_of is not None):
            for i, (info, node_name) in enumerate(zip(group, names)):
                entry = self._stage_one(
                    fwk, info, node_name, reasons[i], cycle
                )
                if entry is not None:
                    cycle.wave.append(entry)
            return
        # streamed: bucket the group's indices by owning store shard,
        # stage shard-by-shard, hand each staged slice off immediately
        buckets: Dict[int, List[int]] = {}
        for i, node_name in enumerate(names):
            sid = (
                shard_of("Pod", group[i].pod.meta.namespace)
                if node_name is not None else -1
            )
            buckets.setdefault(sid, []).append(i)
        handoffs: List[float] = []
        for sid, idxs in buckets.items():
            entries: List[tuple] = []
            for i in idxs:
                entry = self._stage_one(
                    fwk, group[i], names[i], reasons[i], cycle
                )
                if entry is not None:
                    entries.append(entry)
            if sid < 0 or not entries:
                continue
            try:
                with _trace.span("sched.wave_handoff", len(entries)):
                    self._dispatch_subwave_async(entries, sid)
                handoffs.append(self._clock())
            except Exception:  # noqa: BLE001 — hand-off containment:
                # staged (assumed) pods must not strand on the TTL
                logging.getLogger(__name__).exception(
                    "streamed sub-wave hand-off failed; requeueing"
                )
                for e in entries:
                    self._fail_bind(e[0], e[1])
        if handoffs:
            t_end = self._clock()
            for t in handoffs:
                # the commit lead streaming bought this sub-wave over
                # the whole-group hand-off point
                self.metrics.subwave_stream_lead_ms.observe(
                    (t_end - t) * 1000.0
                )

    def _stage_one(self, fwk, info, node_name, reason, cycle):
        """Stage ONE placement (the per-pod tail shared by the whole-wave
        and streamed paths): filter_result veto → assume → Permit.
        Returns a bind-wave entry for the allow path, None when a
        terminal path (park, requeue, WaitOnPermit thread) took the
        pod."""
        stats, failed = cycle.stats, cycle.failed
        t_attempt = self._clock()
        if node_name is not None:
            node_name = fwk.run_filter_result(info.pod, node_name)
            if node_name is None:
                # a later plugin rejected a placement an earlier one
                # may have reserved for (e.g. volume Reserve) — roll
                # the reservations back before parking
                fwk.run_unreserve(info.pod)
        if node_name is None:
            t_fail = _trace.now()
            stats["unschedulable"] += 1
            self.metrics.schedule_attempts.inc("unschedulable")
            self._mark_failed(info, _trace.FAIL_UNSCHEDULABLE)
            parked = self.queue.add_unschedulable(info, reason=reason)
            self.events.eventf(
                info.pod, "Warning", "FailedScheduling",
                f"0 nodes available ({_REASON_TEXT.get(reason, 'unschedulable')})",
            )
            failed.append(info)
            cycle.handled.add(pod_key(info.pod))
            cycle.note_failure(t_fail, parked)
            return None
        try:
            self.cache.assume(info.pod, node_name)
        except (KeyError, ValueError):
            t_fail = _trace.now()
            fwk.run_unreserve(info.pod)
            stats["bind_errors"] += 1
            self.metrics.schedule_attempts.inc("error")
            self._mark_failed(info, _trace.FAIL_ASSUME)
            self.queue.requeue_backoff(info)
            cycle.handled.add(pod_key(info.pod))
            cycle.note_failure(t_fail)
            return None
        # Permit (schedule_one.go:231): reject aborts; wait parks
        # the pod in the waiting map and the binding runs on its own
        # thread blocking in WaitOnPermit (:278) — the scheduling
        # loop moves on, like the reference's async bindingCycle
        verdict, timeout = fwk.run_permit(info.pod, node_name)
        if verdict == "reject":
            t_fail = _trace.now()
            self.cache.forget(info.pod)
            fwk.run_unreserve(info.pod)
            stats["unschedulable"] += 1
            self.metrics.schedule_attempts.inc("unschedulable")
            self.events.eventf(
                info.pod, "Warning", "FailedScheduling",
                f"permit rejected on node {node_name}",
            )
            self._mark_failed(info, _trace.FAIL_PERMIT)
            self.queue.requeue_backoff(info)
            cycle.handled.add(pod_key(info.pod))
            cycle.note_failure(t_fail)
            return None
        if verdict == "wait":
            wp = WaitingPod(info.pod, node_name, timeout)
            self.waiting.add(wp)
            t = threading.Thread(
                target=self._binding_cycle_async,
                args=(fwk, info, node_name, wp, t_attempt),
                name=f"bind-{info.pod.meta.name}",
                daemon=True,
            )
            t.start()
            stats["waiting"] = stats.get("waiting", 0) + 1
            cycle.handled.add(pod_key(info.pod))
            return None
        # staged: assumed + Permit-allowed; the binding stage owns
        # the rest (PreBind -> wave commit -> PostBind)
        stats["scheduled"] += 1
        cycle.handled.add(pod_key(info.pod))
        return (fwk, info, node_name, t_attempt)

    def _bind_tail(self, fwk, info, node_name, t_attempt) -> bool:
        """PreBind -> bind -> PostBind with failure containment: the
        per-pod tail used by WaitOnPermit binding threads, whose pods
        complete outside any wave (the global metrics Registry still
        records them)."""
        try:
            fwk.run_pre_bind(info.pod, node_name)
            self._bind(info.pod, node_name)
        except Exception:
            self._fail_bind(fwk, info)
            return False
        self._finish_bound(fwk, info, node_name, t_attempt)
        return True

    def _finish_bound(
        self, fwk, info, node_name, t_attempt, finish_binding: bool = True
    ) -> None:
        """The success tail of a committed bind: PostBind, Scheduled
        event, TTL countdown, queue drop, metrics."""
        fwk.run_post_bind(info.pod, node_name)
        self.events.eventf(
            info.pod, "Normal", "Scheduled",
            f"Successfully assigned {pod_key(info.pod)} to {node_name}",
        )
        if finish_binding:
            self.cache.finish_binding(info.pod)
        self.queue.done(info.pod)
        self.metrics.schedule_attempts.inc("scheduled")
        self.metrics.scheduling_attempt_duration.observe(
            self._clock() - t_attempt
        )
        self.metrics.pod_scheduling_sli_duration.observe(
            self._clock() - info.initial_attempt_timestamp
        )

    def _binding_cycle_async(
        self, fwk, info, node_name, wp, t_attempt
    ) -> None:
        """WaitOnPermit then the bind tail, on a binding thread
        (schedule_one.go:118's goroutine).  Rejection/timeout forgets the
        assume, rolls back reservations, and requeues with backoff."""
        try:
            verdict = wp.wait()
        finally:
            self.waiting.remove(info.pod)
        if verdict != "allow":
            self.cache.forget(info.pod)
            fwk.run_unreserve(info.pod)
            self.metrics.schedule_attempts.inc("unschedulable")
            self.events.eventf(
                info.pod, "Warning", "FailedScheduling",
                f"permit {verdict} on node {node_name}",
            )
            self.queue.requeue_backoff(info)
            return
        self._bind_tail(fwk, info, node_name, t_attempt)

    def _volume_reserve_plugin(
        self, pod: api.Pod, node_name: str
    ) -> Optional[str]:
        """Reserve (volume_binding.go:369): pick concrete volumes for the
        pod's unbound claims on the chosen node; rejecting the placement
        parks the pod for retry (the solve's selector already restricted
        candidates to topology-feasible nodes, so rejection here means a
        race on volume capacity)."""
        if not any(v.persistent_volume_claim for v in pod.spec.volumes):
            return node_name
        try:
            node = self.store.get("Node", node_name, namespace="")
        except KeyError:
            return None
        return node_name if self.volumes.reserve(pod, node) else None

    def _device_reserve_plugin(
        self, pod: api.Pod, node_name: str
    ) -> Optional[str]:
        """DRA Reserve: assume claim allocations on the chosen node."""
        if not pod.spec.resource_claims:
            return node_name
        try:
            node = self.store.get("Node", node_name, namespace="")
        except KeyError:
            return None
        return node_name if self.devices.reserve(pod, node) else None

    def _preempt_plugin(self, pod: api.Pod) -> Optional[str]:
        """The DefaultPreemption PostFilter plugin (registered on every
        profile; replaceable/augmentable via Framework.register)."""
        if not self.preemption.eligible(pod):
            return None
        result = self.preemption.preempt(pod)
        return result.nominated_node if result else None

    def _reject_unencodable(
        self,
        batch: List[QueuedPodInfo],
        fwk: Optional[Framework] = None,
        cycle: Optional["_Cycle"] = None,
    ) -> List[QueuedPodInfo]:
        """Batch encode failed: find the offending pods by encoding each
        alone against the SAME profile's builder (rare path; the per-pod
        encode is the authoritative validation) and park them
        unschedulable.  Returns the encodable remainder."""
        tpu = fwk.tpu if fwk is not None else self.tpu
        good: List[QueuedPodInfo] = []
        for info in batch:
            try:
                tpu.encode_pending([info.pod], lock=self.cache.lock)
                good.append(info)
            except (OverflowError, ValueError):
                t_fail = _trace.now()
                if cycle is not None:
                    cycle.handled.add(pod_key(info.pod))
                self.metrics.schedule_attempts.inc("error")
                self._mark_failed(info, _trace.FAIL_UNENCODABLE)
                # only a pod UPDATE (spec change) can help — no cluster
                # event wakes this reason (queue.move_for_event)
                parked = self.queue.add_unschedulable(
                    info, reason=assign_ops.REASON_UNENCODABLE
                )
                if cycle is not None:
                    cycle.note_failure(t_fail, parked)
        return good

    def _bind(self, pod: api.Pod, node_name: str) -> None:
        """The DefaultBinder POST pods/{name}/binding analogue: write
        nodeName through the API with optimistic concurrency."""
        current = self.store.get("Pod", pod.meta.name, pod.meta.namespace)
        current.spec.node_name = node_name
        current.status.phase = "Running"
        self.store.update(current, copy_result=False)

    # -- warmup ------------------------------------------------------------

    def warmup(self, pods: List[api.Pod], max_batch: Optional[int] = None) -> float:
        """Pre-compile the solver executables a coming workload will hit.

        The reference needs nothing like this (Go compiles ahead of
        time); here first-shape XLA compiles are 10-40 s each, and a
        measured scheduling window that includes them loses the wall
        clock at small scale.  Warmup runs the REAL scheduling path —
        encode + solve, placements discarded, nothing assumed or bound —
        over every power-of-two pod bucket up to the first full batch,
        using caller-supplied template pods so the compiled feature set
        (spread/interpod/ports/...) and constraint-table shapes match
        the workload's.  Combined with the persistent compilation cache
        (utils/compilecache.py) later processes warm in milliseconds.

        What it enumerates is the executable key set of the templates
        (docs/scheduler_loop.md, "What makes an executable new"): every
        shape but the pod bucket is a function of the deployment — the
        unconstrained spec-class dim floors at what ALL of `pods` fill
        (SnapshotBuilder.fix_spec_classes: a deployment of two request
        shapes hands pods of both, and a live batch of one shape then
        takes the executable of a batch of both), the constraint
        class dims and row dims floor at 32 (vocab.pad_constraint_dim),
        a coupled batch's wave plan has one row a pod
        (ops.assign.wave_rows), topo_z and the slot tuples come from
        the cluster and the templates — so a bucket solved here is the
        executable a live batch of that bucket asks for, whatever its
        composition.

        Round A solves every bucket against the current (typically
        bound-pod-free) cluster.  Then one template pod is assumed:
        the bound_* FeatureFlags flip once the first batch binds, which
        is a NEW executable, so templates with spread constraints or
        inter-pod terms get round B, every bucket again (for
        constraint-free pods the count tables have no rows and the
        bits cannot flip).  With the pod still assumed, every template
        set warms what only a cluster that changes between solves
        meets: a batch that leaves pad rows (a first-seen class: the
        PartialsCache's insert path), one dirty row (its refresh path)
        and the mirror's usage scatter at every dirty-row bucket its
        delta path serves: what a bind wave leaves and what the pods
        that left the cluster since the last encode leave, which no
        batch size bounds (mirror.warm_usage_buckets).

        Before it returns it waits for the prewarm pool's outstanding
        jobs (the neighbour keys its own solves offered), so nothing is
        building on any thread afterwards.

        Returns seconds spent.  Never raises: a bucket that fails to
        encode (cap overflow) is skipped — the real cycle handles those
        pods through its own rejection path."""
        t0 = self._clock()
        if not pods or not self.tpu.state._rows:
            return 0.0
        fwk = self.profiles.for_pod(pods[0]) or self.profiles.default
        cap = min(len(pods), max_batch or self.batch_size)
        from ..utils import vocab as vb

        log = logging.getLogger(__name__)
        # the spec-class dim of every later batch floors at what ALL the
        # templates handed here fill, so a batch holding one request
        # shape of two takes the executable of a batch holding both
        try:
            with self.cache.lock:
                fwk.tpu.builder.fix_spec_classes(pods)
        except Exception:
            # a template the encoder refuses (cap overflow), as in
            # warm_batch below: the buckets that encode are still warmed,
            # at the dim their pods have
            log.exception("warmup: a template pod has no spec signature")

        buckets, b = [], self.tpu.builder.limits.min_pods
        top = vb.pad_dim(cap, self.tpu.builder.limits.min_pods)
        while b <= top:
            buckets.append(b)
            b *= 2

        def warm_batch(bucket: int, n_pods: int) -> None:
            try:
                fwk.tpu.schedule_pending(
                    pods[:n_pods], num_pods_hint=bucket, lock=self.cache.lock,
                )
            except Exception:
                # device compile/runtime faults were already contained
                # (and logged with their executable key) by the solver's
                # breaker; what reaches here is an encode failure
                log.exception(
                    "warmup skipped on %s: pod bucket %d over %d nodes "
                    "(%d template pods) failed to encode",
                    device_label(), bucket, len(self.tpu.state._rows),
                    len(pods[:n_pods]),
                )

        def warm_all() -> None:
            # buckets in parallel: encode serializes under the cache
            # lock, but XLA compiles release the GIL and overlap —
            # cold warmup is compile-dominated
            from concurrent.futures import ThreadPoolExecutor

            with ThreadPoolExecutor(max_workers=4) as ex:
                list(ex.map(lambda b: warm_batch(b, b), reversed(buckets)))

        needs_bound_round = any(
            p.spec.topology_spread_constraints
            or (p.spec.affinity and (p.spec.affinity.pod_affinity
                                     or p.spec.affinity.pod_anti_affinity))
            for p in pods
        )
        def join_prewarm() -> None:
            # the pool compiles neighbour keys off this thread; its last
            # job would otherwise end after warmup returned
            pool = fwk.tpu.prewarm_pool
            if pool is not None and not pool.join():
                log.warning("warmup: the prewarm pool was still building "
                            "when its wait ran out")

        warm_all()
        clone = copy.deepcopy(pods[0])
        clone.meta.name = "warmup-bound-pod"
        clone.meta.namespace = pods[0].meta.namespace or "default"
        node0 = next(iter(self.tpu.state._rows))
        try:
            self.cache.assume(clone, node0)  # graftlint: disable=obligations -- the finally below forgets the clone; if THAT forget fails it is logged and cleanup_expired retires the synthetic assume by TTL
        except Exception:
            join_prewarm()
            return self._clock() - t0  # no usable node; round A ran
        try:
            if needs_bound_round:
                warm_all()
            warm_batch(buckets[0], max(buckets[0] - 1, 1))
            mirror = fwk.tpu._mirror if fwk.tpu.use_mirror else None
            if mirror is not None:
                with self.cache.lock:
                    mirror.warm_usage_buckets()
        except Exception:
            log.exception("warmup: warming the delta paths failed")
        finally:
            try:
                self.cache.forget(clone)
            except Exception:
                log.exception("warmup: forgetting the bound clone failed")
        join_prewarm()
        return self._clock() - t0

    # -- test convenience -------------------------------------------------

    def wait_for_idle(self, timeout: float = 30.0) -> bool:
        """True once no pending pods remain in active/backoff/inflight
        (unschedulable pods may remain parked)."""
        deadline = self._clock() + timeout
        while self._clock() < deadline:
            s = self.queue.stats()
            if s["active"] == 0 and s["inflight"] == 0 and s["backoff"] == 0:
                return True
            time.sleep(0.02)
        return False
