"""TPUBatchScheduler — the flagship model: snapshot in, assignments out.

Wraps the ops kernels into the one-dispatch scheduling step the rest of
the framework (host scheduler, extender endpoint, benchmarks) calls.  The
north-star replacement for the reference's per-pod scheduling cycle
(pkg/scheduler/schedule_one.go:66): one compiled program filters, scores,
and assigns an entire pending batch with assume-bookkeeping carried on
device.

Two solver paths, routed automatically:
  * greedy scan (ops.assign) — exact one-pod-at-a-time reference
    semantics; handles every constraint family, including gang
    all-or-nothing via its post-pass (ops.assign n_groups).
  * auction (ops.auction) — joint parallel solve for large bursts and
    gang groups; static+resource families only.

Gangs therefore keep all-or-nothing semantics on BOTH routes: a gang
carrying spread/interpod/port constraints routes to greedy and its
incomplete placements are released by the post-pass.

Cluster state is incremental (ops.schema.ClusterState): node and pod
changes touch one tensor row, and per-batch encode cost is O(pending),
the cache.go:185-260 UpdateSnapshot property.
"""

from __future__ import annotations

import contextlib
import logging
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis import epochs, retrace
from ..analysis import ledger as _ledger
from ..analysis.markers import hot_path
from ..api import types as api
from ..ops import assign as assign_ops
from ..ops import auction as auction_ops
from ..ops import partials as partials_ops
from ..ops import schema
from ..ops.scores import DEFAULT_SCORE_CONFIG, ScoreConfig
from ..testing import faults
from ..utils import trace
from .mirror import DeviceClusterMirror, pad_rows, scatter_usage
from .partials import PartialsCache

Result = Union[assign_ops.SolveResult, auction_ops.AuctionResult]

_log = logging.getLogger(__name__)


def device_label() -> str:
    """The device JAX solves on, as "platform:device_kind xN".  Every log
    line that reports a device-path failure carries it, so a kernel one
    backend's compiler refuses reads as that and not as a generic
    fault.  Cannot raise: it is evaluated inside the handlers that guard
    the retry and the host fallback, and a label must not cost them."""
    try:
        devs = jax.devices()
        return f"{devs[0].platform}:{devs[0].device_kind} x{len(devs)}"
    except Exception:  # noqa: BLE001
        return "unknown device"


def _executable_key(meta: schema.SnapshotMeta, solved) -> str:
    """The static half of a solve's jit key, for failure logs: the
    routing statics plus the padded node and pod buckets, read off the
    Snapshot that was dispatched or the SolveResult that came back.
    Cannot raise, for the same reason as device_label."""
    try:
        pod_axis = (
            solved.assignment if hasattr(solved, "assignment")
            else solved.pods.req
        )
        flags = meta.features._asdict() if meta.features is not None else {}
        return (
            f"route={meta.route} "
            f"nodes={solved.cluster.allocatable.shape[0]} "
            f"pods={pod_axis.shape[0]} "
            f"topo={meta.topo_split} groups={meta.n_groups} "
            f"warm={meta.statics is not None} features="
            f"{{{', '.join(f'{k}={v}' for k, v in flags.items() if v)}}}"
        )
    except Exception:  # noqa: BLE001
        return "unknown executable"


class SolveUnhealthy(RuntimeError):
    """The device returned a structurally-broken solve (non-finite score
    for a placed pod, NaN anywhere in the score tensor): the placements
    cannot be trusted.  Treated exactly like an XLA runtime error by the
    circuit breaker."""


class SolveCircuitBreaker:
    """Device-solve circuit breaker (the kube pattern: contain a failing
    dependency, probe for recovery).

    closed     → device solves flow normally.
    open       → the device path failed twice in a row (one retry);
                 every batch routes to the host fallback until the
                 cooldown elapses.
    half-open  → cooldown elapsed: ONE batch probes the device; success
                 closes the breaker, failure re-opens it with a fresh
                 cooldown.

    The breaker deliberately has no failure-rate window: the device
    solve is all-or-nothing per batch, so consecutive-failure semantics
    (fail → retry → trip) match the dispatch shape."""

    CLOSED, HALF_OPEN, OPEN = "closed", "half_open", "open"
    _STATE_CODE = {CLOSED: 0.0, HALF_OPEN: 1.0, OPEN: 2.0}

    GUARDED_FIELDS = {
        "state": "_lock",
        "_open_until": "_lock",
        "trips": "_lock",
        "fallbacks": "_lock",
        "probes": "_lock",
    }

    def __init__(self, cooldown: float = 5.0, clock=time.monotonic):
        self.cooldown = cooldown
        self._clock = clock
        self._lock = threading.Lock()
        self.state = self.CLOSED
        self._open_until = 0.0
        self.trips = 0       # CLOSED/HALF_OPEN -> OPEN transitions
        self.fallbacks = 0   # batches solved on the host path
        self.probes = 0      # half-open device attempts

    def state_code(self) -> float:
        # the metrics reader is another thread (a /metrics scrape) while
        # dispatch threads transition the breaker — take the lock (the
        # unlocked read was a graftlint guarded-by finding)
        with self._lock:
            return self._STATE_CODE[self.state]

    def record_fallback(self) -> None:
        """Count a batch solved on the host path (called by the owner's
        _host_fallback — the counter shares the breaker mutex)."""
        with self._lock:
            self.fallbacks += 1

    def fallback_count(self) -> int:
        with self._lock:
            return self.fallbacks

    def allow_device(self) -> bool:
        """True when this batch may use the device: closed, or open with
        the cooldown elapsed (the call transitions to half-open and the
        batch becomes the probe)."""
        with self._lock:
            if self.state == self.CLOSED:
                return True
            if self.state == self.OPEN and self._clock() >= self._open_until:
                self.state = self.HALF_OPEN
                self.probes += 1
                return True
            # open inside the cooldown, or half-open with the probe
            # already in flight on another thread
            return False

    def record_success(self) -> None:
        with self._lock:
            if self.state != self.CLOSED:
                self.state = self.CLOSED

    def record_failure(self) -> None:
        with self._lock:
            self.trips += 1
            self.state = self.OPEN
            self._open_until = self._clock() + self.cooldown

    def reset(self) -> None:
        """Snap the breaker to closed with no cooldown pending.
        Leadership reconciliation uses this on takeover/restart: the
        open state belongs to the predecessor's device history — the new
        leader re-probes the device instead of inheriting a cooldown it
        never observed (worst case is one retry + re-trip)."""
        with self._lock:
            self.state = self.CLOSED
            self._open_until = 0.0


class DispatchArbiter:
    """Device-admission control for concurrent profile LANES sharing one
    device/mesh (docs/scheduler_loop.md, pipelined multi-lane cycle).

    Each lane runs its own pop→encode→solve pipeline; encodes already
    serialize under the scheduler-cache lock, but device DISPATCH must
    be arbitrated: the arbiter bounds in-flight device solves to `depth`
    (default 2 — double-buffering: lane A's batch N+1 dispatches while
    batch N reads back, and a third program can't pile onto the device
    queue ahead of another lane's turn).  A slot is released by
    DeviceSolve's coalesced decode (or an explicit release on the
    mis-speculation invalidation path).

    The wait is deadline-bounded as a safety valve: a leaked slot (a
    caller that dispatched and never decoded) degrades fairness, never
    wedges a lane — forced admissions are counted in `forced`."""

    GUARDED_FIELDS = {"_inflight": "_cv", "acquires": "_cv", "forced": "_cv"}

    def __init__(self, depth: int = 2, timeout: float = 30.0,
                 clock=time.monotonic):
        self.depth = max(int(depth), 1)
        self.timeout = timeout
        self._clock = clock
        self._cv = threading.Condition()
        self._inflight = 0
        self.acquires = 0
        self.forced = 0

    def acquire(self) -> bool:  # graftlint: disable=purity -- lane admission: the slot wait IS the arbitration; uncontended cost is one mutex acquire
        """Take a dispatch slot; False means the deadline expired and
        admission was forced (the safety valve, not the normal path)."""
        with self._cv:
            self.acquires += 1
            deadline = self._clock() + self.timeout
            while self._inflight >= self.depth:
                remaining = deadline - self._clock()
                if remaining <= 0:
                    self.forced += 1
                    self._inflight += 1
                    _ledger.push("slot", id(self))
                    return False
                self._cv.wait(min(remaining, 0.2))
            self._inflight += 1
            _ledger.push("slot", id(self))
            return True

    def release(self) -> None:  # graftlint: disable=purity -- slot return; reached from the decode path, not between dispatch and readback
        with self._cv:
            # the ledger pop sits BEFORE the below-zero guard on purpose:
            # the guard keeps production counters sane, but a release with
            # no matching acquire is exactly the double-discharge the
            # GRAFTLINT_OBLIGATIONS ledger exists to surface
            _ledger.pop("slot", id(self))
            if self._inflight > 0:
                self._inflight -= 1
            self._cv.notify_all()

    def inflight(self) -> int:
        with self._cv:
            return self._inflight


class HostSolve:
    """A completed host-fallback solve quacking like DeviceSolve: names
    are already materialized, there is no device future to read back and
    no reason tensor (pods it cannot place park with reason -1 and are
    woken by every event — acceptable in degraded mode)."""

    result = None
    wave_count = None
    wave_fallbacks = None
    wave_steps = None
    frag_score = None
    carveouts = None
    contiguous_gangs = None
    carveout_fallbacks = None

    def __init__(self, names: List[Optional[str]]):
        self._names = names
        self.encode_s = 0.0
        self.dispatch_s = 0.0
        self.decode_wait_s = 0.0
        self.deferred_s = 0.0
        self.dispatched_at = time.perf_counter()

    def ready(self) -> bool:
        return True

    def names(self) -> List[Optional[str]]:
        return self._names

    def reasons(self) -> Optional[List[int]]:
        return None

    def release_slot(self) -> None:
        """No-op: the host fallback never held a dispatch slot."""

    def executable_key(self) -> str:
        return "host solve (no executable)"


_FILL_CACHE_MAX = 64  # entries; shape buckets churn as the cluster grows —
                      # evict wholesale so retired multi-MB fills don't pin
                      # device memory forever


def _device_fill_shortcut(
    snap: schema.Snapshot,
    cache: Optional[dict] = None,
    no_bound_pods: bool = False,
    features=None,
    put=None,
    issued: Optional[_Issued] = None,
) -> schema.Snapshot:
    """Replace constant-filled pod/constraint tables with (cached)
    device-side fills before transfer.

    The [T, N] / [C, N] / [U, N] per-node count arrays (bound pods
    matching each spread/interpod/preferred row) dominate snapshot bytes
    at scale — 67MB for a 20k-node anti-affinity batch — yet burst
    workloads have no bound pods at all, so they are zeros.  Likewise
    most batches carry no host ports / tolerations / preferred terms, so
    those [P, ·] tables are constant 0 or -1.  The fills are cached by
    (shape, dtype, value): device arrays are immutable, so one fill
    serves every later snapshot — a fresh jnp.full per leaf per step
    costs a device dispatch each, ~20 dispatches per batch for constant
    leaves.  The cluster half is skipped — it lives in the device mirror
    already.

    put: device placement for the fills and pre-wrapped transfers —
    mesh mode passes a replicated-NamedSharding device_put so every
    leaf lands on the same device set as the sharded mirror (mixing
    single-device-committed and mesh-committed jit operands is a
    placement error).  issued: counts the fills' programs and the
    pre-wrapped transfers."""
    import jax.numpy as jnp

    if put is None:
        put = jax.device_put
    if issued is None:
        issued = _Issued()

    def fill(shape, dtype, value):
        key = (shape, np.dtype(dtype).str, value)
        if cache is None:
            issued.programs += 1
            return put(jnp.full(shape, value, dtype))
        hit = cache.get(key)
        if hit is None:
            if len(cache) >= _FILL_CACHE_MAX:
                cache.clear()
            issued.programs += 1
            hit = cache[key] = put(jnp.full(shape, value, dtype))
        return hit

    def shortcut(arr):
        a = np.asarray(arr)
        if a.size < 65536:  # transfer beats two scans + a fill kernel
            return arr
        lo = a.min()
        if lo != a.max():
            return arr
        return fill(a.shape, a.dtype, lo.item())

    def mark(arr, is_zero):
        """Bound-count table: zero by construction (replace, no scan) or
        known-nonzero from features_of's .any() (transfer, no re-scan)."""
        a = np.asarray(arr)
        if a.size < 65536:
            return arr
        if is_zero:
            return fill(a.shape, a.dtype, 0.0)
        issued.transfers += 1
        return put(a)  # pre-wrap: skips shortcut's min/max

    spread_z = terms_z = pref_z = no_bound_pods
    if features is not None and not no_bound_pods:
        spread_z = not features.bound_spread
        terms_z = not features.bound_terms
        pref_z = not features.bound_pref
    if no_bound_pods or features is not None:
        snap = snap._replace(
            spread=snap.spread._replace(
                node_matches=mark(snap.spread.node_matches, spread_z)
            ),
            terms=snap.terms._replace(
                node_matches=mark(snap.terms.node_matches, terms_z),
                node_owners=mark(snap.terms.node_owners, terms_z),
            ),
            prefpod=snap.prefpod._replace(
                node_counts=mark(snap.prefpod.node_counts, pref_z),
                owner_weight=mark(snap.prefpod.owner_weight, pref_z),
            ),
        )

    def passthrough(arr):
        return arr if isinstance(arr, jax.Array) else shortcut(arr)

    rest = jax.tree.map(passthrough, snap._replace(cluster=None))
    return rest._replace(cluster=snap.cluster)


class _Issued:
    """Host→device transfers and device programs counted by the encode's
    own staging helpers (span sched.encode.stage adds the mirror's and
    the partials' counters to these)."""

    __slots__ = ("transfers", "programs")

    def __init__(self):
        self.transfers = 0
        self.programs = 0


class _Stage(NamedTuple):
    """What the staging program carries beside the pod leaves: host
    segments `segs` = (usage row indices, the rows of requested,
    nonzero_requested and port_bits at them, the partials' refresh
    indices, the batch's slot array), each row segment
    PartialsCache.ROW_CHUNK long and padded with the node bucket (the
    program's scatters drop those), and the device residents it applies
    them to."""

    segs: tuple
    cluster: schema.ClusterTensors
    store: partials_ops.PartialsStore
    specs: partials_ops.ClassSpecs


def _segment(b, spec):
    shape, dt, seg_bytes, o = spec
    seg = jax.lax.slice(b, (o,), (o + seg_bytes,))
    return seg.view(np.dtype(dt)).reshape(shape)


def _unpack_program(specs):
    def _unpack(b):
        return tuple(_segment(b, spec) for spec in specs)

    return jax.jit(_unpack)


def _stage_program(specs, stage_specs):
    """The staging program of one layout: in order, scatter the usage
    rows into the resident mirror leaves, refresh the partials' dirty
    columns against the updated leaves, gather the batch's statics by
    slot, and unpack the pod leaves.  Returns (usage leaves, store,
    statics, pod leaves).  Nothing is donated: the old residents may be
    a speculation bookmark's."""

    def _stage(b, cluster, store, class_specs):
        uidx, *vals, ridx, slots = (_segment(b, spec) for spec in stage_specs)
        usage = scatter_usage(cluster, uidx, vals)
        store = partials_ops.refresh_rows(
            store, class_specs, cluster._replace(**usage), ridx
        )
        statics = partials_ops.gather_statics(store, slots)
        return usage, store, statics, tuple(
            _segment(b, spec) for spec in specs
        )

    return jax.jit(_stage)


def _packed_device_put(
    tree, unpack_cache: dict, put=None, stage: Optional[_Stage] = None,
    issued: Optional[_Issued] = None,
):
    """device_put with all host leaves coalesced into ONE transfer.

    A Snapshot has ~40 host-side pod/constraint leaves and a naive
    device_put issues one transfer per leaf whatever its size.  Here
    the host leaves are concatenated into a single byte buffer and
    sliced/bitcast back into their shapes by one jitted unpack program,
    cached per layout: one transfer and one unpack dispatch per batch.
    Device-resident leaves (mirror tensors, cached fills) pass through
    untouched.

    With `stage` the buffer also carries the stage's segments, after the
    pod leaves, and the layout's program is the staging program
    (_stage_program), which applies them to the stage's residents before
    it unpacks; the result is then (tree, (usage leaves, store,
    statics)).  Its key is the unpack's layout plus the segments', which
    are fixed per deployment: one program per layout either way.

    The staging buffer is double-buffered per layout instead of freshly
    allocated per batch: the allocate+zero of a multi-MB buffer every
    step showed up in encode profiles, and a layout recurs every batch
    once shapes warm up.  device_put returns BEFORE the runtime has
    read the host memory (on a TPU v5e a buffer overwritten right after
    the call reached the device corrupted; the CPU backend may alias it
    outright), so each slot keeps one output of the program that
    last consumed it and is rewritten only once that output is ready:
    the program has run, so the copy out of the host buffer is over.
    With two alternating slots that wait is for the batch before last
    and returns at once in steady state; the lifetime no longer rests
    on how deep the caller pipelines.

    put: placement for the staging buffer (mesh mode passes a
    replicated-NamedSharding device_put — see _device_fill_shortcut).
    issued: counts the transfers and programs."""
    if put is None:
        put = jax.device_put
    if issued is None:
        issued = _Issued()
    leaves, treedef = jax.tree.flatten(tree)
    host_idx = [i for i, l in enumerate(leaves) if not isinstance(l, jax.Array)]
    if stage is None and len(host_idx) <= 2:
        # put only the host leaves: re-putting the device-resident ones
        # (the sharded mirror tensors under a mesh) would reshard them
        for i in host_idx:
            leaves[i] = put(leaves[i])
        issued.transfers += len(host_idx)
        return jax.tree.unflatten(treedef, leaves)
    arrs = [np.ascontiguousarray(leaves[i]) for i in host_idx]
    extra = (
        [] if stage is None else [np.ascontiguousarray(a) for a in stage.segs]
    )
    offsets, off = [], 0
    for a in arrs + extra:
        off = (off + 3) & ~3  # 4-byte align each segment
        offsets.append(off)
        off += a.nbytes
    layout = tuple(
        (a.shape, a.dtype.str, a.nbytes, o)
        for a, o in zip(arrs + extra, offsets)
    )
    specs, stage_specs = layout[: len(arrs)], layout[len(arrs):]
    key = specs if stage is None else (specs, stage_specs)
    nbytes = (off + 3) & ~3
    entry = unpack_cache.get(key)
    if entry is None:
        if len(unpack_cache) >= _FILL_CACHE_MAX:
            unpack_cache.clear()  # retired layouts: drop their executables
        entry = unpack_cache[key] = {
            "program": (
                _unpack_program(specs) if stage is None
                else _stage_program(specs, stage_specs)
            ),
            "bufs": [None, None],
            "consumed": [None, None],  # a program output per slot
            "flip": 0,
        }
    flip = entry["flip"]
    entry["flip"] = flip ^ 1
    buf = entry["bufs"][flip]
    if buf is None or buf.nbytes < nbytes:
        buf = entry["bufs"][flip] = np.zeros(nbytes, dtype=np.uint8)
    elif entry["consumed"][flip] is not None:
        entry["consumed"][flip].block_until_ready()
    for a, o in zip(arrs + extra, offsets):
        buf[o : o + a.nbytes] = a.view(np.uint8).ravel()
    program = entry["program"]
    issued.transfers += 1
    issued.programs += 1
    if stage is None:
        outs = program(put(buf[:nbytes]))
        applied = None
        # layout churn recompiles the unpack program: report it to the
        # recompile-discipline tracker like the solver dispatches (specs
        # IS the executable key here)
        retrace.note("snapshot-unpack", program, lambda: specs)
    else:
        usage, store, statics, outs = program(
            put(buf[:nbytes]), stage.cluster, stage.store, stage.specs
        )
        applied = (usage, store, statics)
        # the residents' shapes are fixed per deployment (cap, n, r)
        # but belong to the key all the same
        retrace.note(
            "snapshot-stage", program,
            lambda: (key, tuple(
                np.shape(a)
                for a in jax.tree.leaves((stage.cluster, stage.store))
            )),
        )
    entry["consumed"][flip] = outs[0] if stage is None else applied[2].sfeas
    for i, out in zip(host_idx, outs):
        leaves[i] = out
    tree = jax.tree.unflatten(treedef, leaves)
    return tree if stage is None else (tree, applied)


class DeviceSolve:
    """A dispatched solve held as device futures.

    JAX dispatch is asynchronous: the arrays inside `result` are promises
    the device is still computing.  The decode (device→host readback) is
    deferred until `names()`/`reasons()` is first called, and then runs
    as ONE coalesced device_get of every array the caller will need —
    the previous path paid separate blocking np.asarray round-trips for
    assignment and reasons.  Deferral lets a caller put host work of its
    own between the dispatch and the decode; `deferred_s` is that gap.
    The scheduling loop has none to put there (collecting arrivals costs
    the lane nothing): it decodes at once and sleeps in the device_get,
    interpreter lock released, for as long as the device takes."""

    def __init__(self, result: Result, meta: schema.SnapshotMeta):
        self.result = result
        self.meta = meta
        # the recorder's clock (utils/trace.py); schedule_pending_async
        # replaces it by the instant its dispatch span closed
        self.dispatched_at = trace.now()
        self._decoded = None
        # DispatchArbiter slot held for this in-flight solve (multi-lane
        # admission); released by the coalesced decode, or explicitly by
        # the mis-speculation invalidation path (which never decodes)
        self._slot: Optional[DispatchArbiter] = None
        # step wall split, filled by schedule_pending_async / _decode
        self.encode_s = 0.0        # snapshot encode (under the cache lock)
        self.dispatch_s = 0.0      # jit trace/compile + dispatch enqueue
        self.decode_wait_s = 0.0   # time blocked inside device_get
        self.deferred_s = 0.0      # dispatch -> decode-start gap (overlap)

    def ready(self) -> bool:
        """Non-blocking: has the device finished the solve?  Mesh-mode
        results are sharded jax Arrays and answer is_ready like any
        other future — the sharded solve rides the same deferred
        single-coalesced-readback path (decode overlap survives
        sharding)."""
        try:
            return bool(self.result.assignment.is_ready())
        except AttributeError:  # host numpy result (raw-kernel callers)
            return True

    def executable_key(self) -> str:
        """What a readback failure of this solve is logged under."""
        return _executable_key(self.meta, self.result)

    def release_slot(self) -> None:
        """Give the dispatch-arbiter slot back (idempotent).  Runs from
        the decode's finally and from the invalidation path."""
        slot, self._slot = self._slot, None
        if slot is not None:
            slot.release()

    def _decode(self):
        if self._decoded is None:
            tree = {
                "assignment": self.result.assignment,
                "scores": getattr(self.result, "scores", None),
                "reasons": self.result.reasons,  # None stays None
                "wave_count": getattr(self.result, "wave_count", None),
                "wave_fallbacks": getattr(self.result, "wave_fallbacks", None),
                "wave_steps": getattr(self.result, "wave_steps", None),
                # slice carve-out telemetry (None off the slice family)
                "frag_score": getattr(self.result, "frag_score", None),
                "carveouts": getattr(self.result, "carveouts", None),
                "contiguous_gangs": getattr(
                    self.result, "contiguous_gangs", None
                ),
                "carveout_fallbacks": getattr(
                    self.result, "carveout_fallbacks", None
                ),
            }
            with trace.span("sched.decode_wait", self.meta.num_pods) as sp:
                try:
                    got = jax.device_get(tree)  # one coalesced readback
                finally:
                    # the device finished (or failed) this program — the
                    # next lane's dispatch may proceed either way
                    self.release_slot()
            # the span's two reads are the timings: one measurement
            self.deferred_s = sp.t0 - self.dispatched_at
            self.decode_wait_s = sp.t1 - sp.t0
            assignment = np.asarray(got["assignment"])
            # health check (the circuit breaker's non-finite-score trip
            # wire): a NaN score, or a placed pod whose winning score is
            # non-finite, means the solve state is corrupt and none of
            # this batch's placements can be trusted
            if got["scores"] is not None:
                s = np.asarray(got["scores"])[: self.meta.num_pods]
                placed = assignment[: self.meta.num_pods] >= 0
                if np.isnan(s).any() or not np.isfinite(s[placed]).all():
                    raise SolveUnhealthy(
                        "non-finite score tensor in device solve"
                    )
            self._decoded = (
                assignment,
                None if got["reasons"] is None else np.asarray(got["reasons"]),
                None if got["wave_count"] is None else int(got["wave_count"]),
                None if got["wave_fallbacks"] is None
                else int(got["wave_fallbacks"]),
                None if got["frag_score"] is None
                else float(got["frag_score"]),
                None if got["carveouts"] is None else int(got["carveouts"]),
                None if got["contiguous_gangs"] is None
                else int(got["contiguous_gangs"]),
                None if got["carveout_fallbacks"] is None
                else int(got["carveout_fallbacks"]),
                None if got["wave_steps"] is None else int(got["wave_steps"]),
            )
        return self._decoded

    def names(self) -> List[Optional[str]]:
        assignment = self._decode()[0][: self.meta.num_pods]
        return [self.meta.node_name(int(i)) for i in assignment]

    def reasons(self) -> Optional[List[int]]:
        decoded = self._decode()[1]
        if decoded is None:
            return None
        return [int(r) for r in decoded[: self.meta.num_pods]]

    @property
    def wave_count(self) -> Optional[int]:
        return self._decode()[2]

    @property
    def wave_fallbacks(self) -> Optional[int]:
        return self._decode()[3]

    @property
    def wave_steps(self) -> Optional[int]:
        """In-wave sequential steps the device ran (None off the
        wavefront route): pods where every wave had one member."""
        return self._decode()[8]

    @property
    def frag_score(self) -> Optional[float]:
        """Post-solve cluster fragmentation (None off the slice family)."""
        return self._decode()[4]

    @property
    def carveouts(self) -> Optional[int]:
        return self._decode()[5]

    @property
    def contiguous_gangs(self) -> Optional[int]:
        return self._decode()[6]

    @property
    def carveout_fallbacks(self) -> Optional[int]:
        return self._decode()[7]


class SolverPrewarmPool:
    """Background executable warm pool.

    First-of-a-bucket batches eat a 10-40 s XLA compile inside
    schedule_batch.  The pool watches the executable keys the dispatch
    path actually uses and speculatively compiles the NEIGHBOR keys a
    workload is about to need — the adjacent pod-size buckets (churn
    batches walk the bucket ladder) and the bound-flags variant (the
    bound_* FeatureFlags flip once the first batch binds, which is a new
    executable; Scheduler.warmup's round B exists for the same reason)
    — off-thread via jit.lower().compile().  With the persistent
    compilation cache (utils.compilecache, wired on package import) the
    AOT compile lands in the on-disk cache, so the later jit call
    "compiles" in milliseconds instead of re-tracing XLA.

    Compiles release the GIL, so the worker does not stall the
    scheduling thread.  close() drains the queue and joins the worker —
    tearing the interpreter down mid-compile aborts the process, so
    every owner must close (TPUBatchScheduler registers atexit)."""

    GUARDED_FIELDS = {"_seen": "_lock", "_thread": "_lock"}

    def __init__(self, compile_observer=None, max_pending: int = 16):
        import queue as _q

        self._q: "_q.Queue" = _q.Queue(maxsize=max_pending)
        self._seen: set = set()
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._stop = False
        self.compile_observer = compile_observer
        self.compiled = 0
        self.errors = 0

    def _ensure_thread(self) -> None:
        with self._lock:
            if self._thread is None or not self._thread.is_alive():
                self._stop = False
                self._thread = threading.Thread(
                    target=self._work, name="solver-prewarm", daemon=False
                )
                self._thread.start()

    def _work(self) -> None:
        import queue as _q

        while True:
            try:
                job = self._q.get(timeout=5.0)
            except _q.Empty:
                return  # idle: let the thread retire; re-spawned on demand
            try:
                if job is None or self._stop:
                    return
                label, compile_fn = job
                t0 = time.perf_counter()
                try:
                    compile_fn()
                    self.compiled += 1
                except Exception:  # noqa: BLE001 — speculative work only
                    self.errors += 1
                    _log.warning(
                        "prewarm compile failed for %s on %s", label,
                        device_label(), exc_info=True,
                    )
                    continue
                if self.compile_observer is not None:
                    try:
                        self.compile_observer(time.perf_counter() - t0)
                    except Exception:  # noqa: BLE001
                        pass
            finally:
                self._q.task_done()     # what join() waits for

    def offer(self, key, label: str, compile_fn) -> bool:
        """Enqueue a speculative compile if its key is new.  Never
        blocks: a full queue drops the job (the synchronous compile
        path still works, just cold)."""
        with self._lock:
            if key in self._seen:
                return False
            self._seen.add(key)
        try:
            self._q.put_nowait((label, compile_fn))
        except Exception:  # noqa: BLE001 — queue full
            return False
        self._ensure_thread()
        return True

    def mark_seen(self, key) -> bool:
        """Record a key the dispatch path compiled synchronously.
        Returns True when the key was new."""
        with self._lock:
            if key in self._seen:
                return False
            self._seen.add(key)
            return True

    def join(self, timeout: float = 120.0) -> bool:
        """Wait until every job offered so far has been built (or has
        failed): Scheduler.warmup returns only then, so that nothing is
        built after it whatever the timing.  False at the timeout."""
        deadline = time.monotonic() + timeout
        done = self._q.all_tasks_done
        with done:
            while self._q.unfinished_tasks:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                done.wait(remaining)
        return True

    def close(self, timeout: float = 60.0) -> None:
        self._stop = True
        try:
            self._q.put_nowait(None)
        except Exception:  # noqa: BLE001
            pass
        with self._lock:
            t = self._thread
        # snapshot join: a respawned thread sees _stop and exits on its
        # own, so joining a superseded handle is safe — stale here is
        # harmless by design
        if t is not None and t.is_alive():  # graftlint: disable=atomicity -- snapshot join; _stop gates respawn
            t.join(timeout=timeout)


class TPUBatchScheduler:
    """Owns the incremental cluster state (persistent vocabularies) and
    the jitted solvers.

    Stateless usage (one-shot):
        sched = TPUBatchScheduler()
        placements = sched.schedule(nodes, pending_pods, bound_pods)

    Incremental usage (the host scheduler's path):
        sched.add_node(n) / sched.remove_node(name)
        sched.assume(pod, node_name) / sched.forget(pod)
        placements = sched.schedule_pending(pending_pods)
    """

    # Greedy-routed batches at least this large solve through the
    # wavefront path (ops.assign.wavefront_assign): below it the classic
    # scan's executable is cheaper to hold and the wave win is noise.
    # Unswept on the chip (ROADMAP D2).  What a sweep weighs: on an
    # 8,192-row node bucket a classic scan step is 20-32 us a pod
    # (PERF_LEDGER, PR 32, perf5k-basic-steady: 0.318 ms for 10 pods),
    # and since PR 33 a wave costs its evaluation plus a step a member,
    # a one-member wave a scan step (PERF.md section 5 has the split).
    WAVEFRONT_MIN_PODS = 64

    def __init__(
        self,
        score_config: ScoreConfig = DEFAULT_SCORE_CONFIG,
        limits: Optional[schema.SnapshotLimits] = None,
        mode: str = "auto",  # auto | greedy | auction
        state: Optional[schema.ClusterState] = None,
        mesh=None,  # jax.sharding.Mesh: shard the solve axis across chips
        solve_shard_axis: str = "node",  # node | pod (wavefront-only twin)
        use_mirror: bool = True,  # DeviceClusterMirror feature gate
        use_wavefront: bool = True,  # wave-parallel greedy feature gate
        wave_cap: int = assign_ops.DEFAULT_WAVE_CAP,
        prewarm: Optional[bool] = None,  # None = auto (off on CPU backend)
        arbiter: Optional[DispatchArbiter] = None,  # shared across lanes
        carveout_policy: str = "prefer",  # slice carve-outs: prefer|require|off
        use_partials: bool = True,  # PartialsCache (IncrementalSolve gate)
        partials_resync_interval: int = PartialsCache.DEFAULT_RESYNC_INTERVAL,
    ):
        if state is not None:
            # shared-state instance: multiple scheduler PROFILES solve the
            # same cluster with different score configs (profile.Map —
            # one frameworkImpl per profile over one cache)
            self.builder = state.builder
            self.state = state
        else:
            self.builder = schema.SnapshotBuilder(limits)
            self.state = schema.ClusterState(self.builder)
        self.score_config = score_config
        self.mode = mode
        self.mesh = mesh
        if solve_shard_axis not in ("node", "pod"):
            raise ValueError(
                f"solve_shard_axis must be node|pod, got "
                f"{solve_shard_axis!r}"
            )
        self.solve_shard_axis = solve_shard_axis
        self.use_wavefront = use_wavefront
        self.wave_cap = wave_cap
        # TPU slice carve-out policy (ops/slices.py): "prefer" biases
        # shaped gangs onto contiguous sub-cuboids, "require" filters on
        # them (a gang that can't fit contiguously parks whole), "off"
        # disarms the family (SchedulerConfiguration.slice_carveout_policy)
        if carveout_policy not in ("prefer", "require", "off"):
            raise ValueError(
                f"carveout_policy must be prefer|require|off, got "
                f"{carveout_policy!r}"
            )
        self.carveout_policy = carveout_policy
        # throughput of the most recent snapshot encode (pods/s over the
        # build_from_state wall time) — what the Registry's
        # scheduler_encode_rows_per_s reads
        self.last_encode_rows_per_s = 0.0
        self._greedy = assign_ops.greedy_assign_jit(score_config)
        self._wavefront = assign_ops.wavefront_assign_jit(score_config)
        self._auction = auction_ops.auction_assign_jit(score_config)
        if prewarm is None:
            # speculative background compiles only pay off where compiles
            # are expensive (real accelerators); CPU test runs skip them
            prewarm = jax.default_backend() not in ("cpu",)
        self.prewarm_pool: Optional[SolverPrewarmPool] = (
            SolverPrewarmPool() if prewarm else None
        )
        if self.prewarm_pool is not None:
            import atexit

            atexit.register(self.prewarm_pool.close)
        if mesh is not None:
            # multi-chip: node axis sharded over the mesh (SURVEY §2.7
            # row 8) — all three solver families have sharded twins with
            # placement parity (tests/test_sharded.py,
            # tests/test_sharded_pipeline.py)
            from jax.sharding import NamedSharding, PartitionSpec
            from ..parallel import sharded as _sharded

            if solve_shard_axis == "pod":
                # pod-axis mesh (PR 16's wide-batch regime): only the
                # wavefront family has a pod-sharded twin — wave members
                # split across chips against replicated node tables and
                # the member axis pads itself to the mesh, so there is
                # no divisibility precondition.  Greedy/auction batches
                # stay single-chip under this axis.
                self._greedy_sharded = self._greedy
                self._wavefront_sharded = _sharded.podsharded_wavefront_jit(
                    mesh, score_config
                )
                self._auction_sharded = self._auction
            else:
                self._greedy_sharded = _sharded.sharded_greedy_jit(
                    mesh, score_config
                )
                self._wavefront_sharded = _sharded.sharded_wavefront_jit(
                    mesh, score_config
                )
                self._auction_sharded = _sharded.sharded_auction_jit(
                    mesh, score_config
                )
            self._mesh_size = int(mesh.devices.size)
            # every host→device transfer in mesh mode targets the mesh's
            # replicated sharding: the solve jits consume the sharded
            # mirror, and jit operands must share one device set
            rep = NamedSharding(mesh, PartitionSpec())
            self._put = lambda x: jax.device_put(x, rep)
        else:
            self._mesh_size = 0
            self._put = jax.device_put
        # batches a configured mesh could not solve sharded (padded node
        # bucket smaller than the mesh) — what
        # scheduler_sharded_solve_fallbacks reads
        self.sharded_fallbacks = 0
        self._mirror = DeviceClusterMirror(self.state, mesh=mesh)
        self.use_mirror = use_mirror
        # device-resident Filter/Score partials warm-starting each solve
        # (the incremental O(changes) path, models/partials.py): keyed
        # by pod-class signatures, scatter-refreshed from the same dirty
        # rows the mirror scatters, invalidated/rolled back alongside
        # it.  Needs the mirror (warm rows evaluate against the resident
        # cluster tensors the solve consumes).
        self._partials: Optional[PartialsCache] = (
            PartialsCache(
                self.state, mesh=mesh,
                resync_interval=partials_resync_interval,
            )
            if use_partials and use_mirror
            else None
        )
        # multi-lane device admission: profile lanes sharing one
        # device/mesh pass ONE DispatchArbiter (FrameworkRegistry wires
        # it for multi-profile configs); None = uncontended single lane,
        # no admission overhead on the dispatch path
        self.arbiter = arbiter
        # device-solve circuit breaker: XLA runtime/compile errors and
        # non-finite score tensors retry once, then trip every batch to
        # the host-side per-pod exact-evaluation fallback for a cooldown
        # (docs/robustness.md)
        self.breaker = SolveCircuitBreaker()
        self._fill_cache: dict = {}
        self._unpack_cache: dict = {}
        self.last_result: Optional[Result] = None
        # the effective solve object of the most recent finalize_pending
        # (the caller's DeviceSolve unless the breaker's retry/fallback
        # replaced it)
        self.last_solve = None
        # encode/solve wall split of the most recent schedule_pending —
        # the host scheduler's pipeline-overlap meter reads it: the
        # encode half holds the cache lock (a concurrent wave commit
        # can't overlap it), only the device half truly pipelines
        self.last_timings: Dict[str, float] = {}

    @property
    def shard_count(self) -> int:
        """Mesh size the solver shards over (0 = single chip) — what
        scheduler_solve_shard_count reads."""
        return self._mesh_size

    # -- incremental cluster state ---------------------------------------

    def add_node(self, node: api.Node) -> None:
        self.state.add_node(node)

    def update_node(self, node: api.Node) -> None:
        self.state.update_node(node)

    def remove_node(self, name: str) -> None:
        self.state.remove_node(name)

    def assume(self, pod: api.Pod, node_name: str) -> None:
        """Account a placement immediately (cache.go AssumePod)."""
        self.state.add_pod(pod, node_name)

    def forget(self, pod: api.Pod) -> None:
        """Undo an assume / remove a bound pod (ForgetPod/RemovePod)."""
        self.state.remove_pod(pod)

    # -- scheduling -------------------------------------------------------

    # Batches at least this large route to the joint auction solve when
    # its constraint coverage allows: the greedy scan's P sequential steps
    # dominate solve latency there, while small batches keep the scan's
    # exact one-at-a-time reference semantics.
    AUCTION_MIN_PODS = 1024

    def _route(
        self,
        snap: schema.Snapshot,
        features: assign_ops.FeatureFlags,
        topo_split: Tuple[int, int],
        n_groups: int,
    ) -> str:
        route = self.mode
        if route == "auto":
            route = "greedy"
            if auction_ops.auction_features_ok(features):
                ok = True
                if features.interpod:
                    # the repair's [P, T] / [Z, T] tables must stay
                    # on-chip — this guard binds even for gang batches
                    # (greedy keeps gang all-or-nothing via its own
                    # post-pass)
                    t_dim = snap.terms.valid.shape[0]
                    if t_dim * max(snap.pods.req.shape[0], topo_split[1]) > 2**25:
                        ok = False
                has_gangs = n_groups > 0
                big = snap.pods.req.shape[0] >= self.AUCTION_MIN_PODS
                if ok and (has_gangs or big):
                    route = "auction"
        if route == "greedy" and (
            self.use_wavefront
            and snap.pods.req.shape[0] >= self.WAVEFRONT_MIN_PODS
            and not features.slices
        ):
            # same semantics as the scan (ops.assign parity suite), one
            # batched evaluation a wave; mesh mode routes here too —
            # the sharded wavefront is scan-identical across shards.
            # Slice carve-out batches stay on the classic scan: every
            # shaped pod writes the free mask every other shaped pod's
            # corner evaluation reads, so wave-start evaluation cannot
            # hold (auction_features_ok excludes them for the same
            # reason — sequential-by-construction anchor semantics).
            route = "wavefront"
        return route

    def _sharded_ok(self, snap: schema.Snapshot, route: str = "greedy") -> bool:
        """True when this batch solves on the mesh.  Node axis: any
        route, but the padded node bucket must split evenly across the
        mesh — a bucket smaller than the mesh (tiny cluster under a
        wide mesh) falls back to the single chip and counts a
        sharded_solve_fallback.  Pod axis: wavefront only (the one
        family with a pod-sharded twin); its member axis pads itself to
        the mesh, so there is no divisibility check, and non-wavefront
        routes run single-chip by design rather than as a fallback."""
        if self.mesh is None:
            return False
        if self.solve_shard_axis == "pod":
            return route == "wavefront"
        if snap.cluster.allocatable.shape[0] % self._mesh_size == 0:
            return True
        self.sharded_fallbacks += 1
        if self.sharded_fallbacks == 1:
            # once per scheduler: the count lives on in
            # scheduler_sharded_solve_fallbacks
            _log.warning(
                "mesh of %d configured on %s but the %d-row node bucket "
                "does not split across it; solving single-chip",
                self._mesh_size, device_label(),
                snap.cluster.allocatable.shape[0],
            )
        return False

    @staticmethod
    def _shapes_of(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree
        )

    @staticmethod
    def _shapes_with_pod_dim(
        shapes: schema.Snapshot, p_new: int
    ) -> schema.Snapshot:
        """Rewrite the pod axis of a Snapshot shape tree to p_new (class/
        constraint-row dims are workload-shaped and stay put)."""

        def redim(sds, axis=0):
            shape = list(sds.shape)
            shape[axis] = p_new
            return jax.ShapeDtypeStruct(tuple(shape), sds.dtype)

        pods = shapes.pods._replace(
            valid=redim(shapes.pods.valid),
            req=redim(shapes.pods.req),
            nonzero_req=redim(shapes.pods.nonzero_req),
            name_id=redim(shapes.pods.name_id),
            sel_idx=redim(shapes.pods.sel_idx),
            tol_bits=redim(shapes.pods.tol_bits, axis=1),
            tol_all=redim(shapes.pods.tol_all, axis=1),
            port_bits=redim(shapes.pods.port_bits),
            pref_idx=redim(shapes.pods.pref_idx),
            pref_weight=redim(shapes.pods.pref_weight),
            class_id=redim(shapes.pods.class_id),
            priority=redim(shapes.pods.priority),
            group_id=redim(shapes.pods.group_id),
            pod_shape=redim(shapes.pods.pod_shape),
        )
        return shapes._replace(
            pods=pods,
            spread=shapes.spread._replace(
                pod_matches=redim(shapes.spread.pod_matches),
                pod_idx=redim(shapes.spread.pod_idx),
            ),
            terms=shapes.terms._replace(
                matches_incoming=redim(shapes.terms.matches_incoming),
                aff_idx=redim(shapes.terms.aff_idx),
                anti_idx=redim(shapes.terms.anti_idx),
                self_match_all=redim(shapes.terms.self_match_all),
            ),
            prefpod=shapes.prefpod._replace(
                matches_incoming=redim(shapes.prefpod.matches_incoming),
                pod_idx=redim(shapes.prefpod.pod_idx),
                pod_weight=redim(shapes.prefpod.pod_weight),
            ),
            images=shapes.images._replace(
                pod_ids=redim(shapes.images.pod_ids),
                n_containers=redim(shapes.images.n_containers),
            ),
        )

    def _prewarm_neighbors(  # graftlint: disable=purity -- speculative compile bookkeeping; the pool mutex is uncontended and compiles run off-thread
        self, snap, route, topo_z, features, n_groups, wave_shape=None,
        sharded: bool = False, statics=None,
    ) -> None:
        """On a first-seen executable key, speculatively compile the keys
        the workload will hit next (SolverPrewarmPool docstring).  The
        key carries the mesh size: sharded and single-chip solves of the
        same bucket are DIFFERENT executables (shard_map is part of the
        program), and a mesh-mode scheduler prewarms the sharded twin.
        Warm-started solves (statics from the PartialsCache) are their
        own executable family: the key carries the statics shapes and
        the compiles target the `.jitted_warm` twin."""
        pool = self.prewarm_pool
        if pool is None or route == "auction":
            return
        p_dim = snap.pods.req.shape[0]
        n_dim = snap.cluster.allocatable.shape[0]
        mesh_key = self._mesh_size if sharded else 0
        statics_key = (
            None
            if statics is None
            else tuple(
                (tuple(a.shape), str(a.dtype)) for a in statics
            )
        )
        key = (
            route, mesh_key, n_dim, p_dim, topo_z, features, n_groups,
            wave_shape, statics_key,
        )
        if not pool.mark_seen(key):
            return
        shapes = self._shapes_of(snap)
        statics_shapes = (
            None if statics is None else self._shapes_of(statics)
        )
        if sharded:
            solver = (
                self._wavefront_sharded if route == "wavefront"
                else self._greedy_sharded
            )
        else:
            solver = (
                self._wavefront if route == "wavefront" else self._greedy
            )
        fn = solver.jitted if statics is None else solver.jitted_warm

        def offer(p_variant, feats):
            wshape = wave_shape
            if route == "wavefront":
                if p_variant != p_dim or wshape is None:
                    wshape = (
                        assign_ops.wave_rows(
                            p_variant, self.wave_cap,
                            assign_ops.waves_couple(features),
                        ),
                        self.wave_cap,
                    )
                args_shapes = (
                    self._shapes_with_pod_dim(shapes, p_variant)
                    if p_variant != p_dim else shapes,
                    jax.ShapeDtypeStruct(wshape, np.int32),
                )
            else:
                args_shapes = (
                    self._shapes_with_pod_dim(shapes, p_variant)
                    if p_variant != p_dim else shapes,
                )
            if statics_shapes is not None:
                # the warm twin takes the statics triple right after the
                # array args; the class axis tracks the batch's class
                # set, not its pod bucket, so neighbor variants reuse it
                args_shapes = args_shapes + (statics_shapes,)
            nkey = (
                route, mesh_key, n_dim, p_variant, topo_z, feats, n_groups,
                wshape, statics_key,
            )

            def compile_fn(args_shapes=args_shapes, feats=feats):
                fn.lower(*args_shapes, topo_z, feats, n_groups).compile()

            pool.offer(nkey, f"{route}/p={p_variant}", compile_fn)

        # the bucket ladder: churn batches walk adjacent pod buckets
        offer(p_dim * 2, features)
        if p_dim // 2 >= self.builder.limits.min_pods:
            offer(p_dim // 2, features)
        # the first bind flips the bound_* gates — a NEW executable the
        # second batch of a constraint workload would compile mid-cycle
        flipped = features._replace(
            bound_spread=features.spread,
            bound_terms=features.interpod,
            bound_pref=features.interpod_pref,
        )
        if flipped != features:
            offer(p_dim, flipped)

    def solve(
        self, snap: schema.Snapshot, topo_z: Optional[int] = None
    ) -> assign_ops.SolveResult:
        """Raw greedy device solve on a prebuilt snapshot.

        topo_z is auto-derived when not given; passing a value smaller
        than required aliases topology domains together and silently
        corrupts spread/inter-pod state, so it is validated (when those
        families are active — it is unused otherwise)."""
        features = assign_ops.features_of(
            snap, slice_policy=self.carveout_policy
        )
        if assign_ops.needs_topo(features):
            required = assign_ops.required_topo_z(snap)
            if topo_z is None:
                topo_z = required
            elif topo_z < required:
                raise ValueError(
                    f"topo_z={topo_z} < required_topo_z={required}: would "
                    "alias topology values (see ops.assign.required_topo_z)"
                )
        return self._greedy(snap, topo_z, features)

    @hot_path
    def _dispatch(
        self, snap: schema.Snapshot, meta: Optional[schema.SnapshotMeta] = None
    ) -> Result:
        meta = meta or schema.SnapshotMeta(0, 0, [], [], self.builder.limits)
        epochs.audit_dispatch(meta)
        features = meta.features or assign_ops.features_of(
            snap, slice_policy=self.carveout_policy
        )
        topo_split = meta.topo_split or assign_ops.required_topo_z_split(snap)
        n_groups = (
            meta.n_groups
            if meta.n_groups is not None
            else schema.num_groups(snap)
        )
        route = meta.route or self._route(snap, features, topo_split, n_groups)
        sharded = self._sharded_ok(snap, route)
        if route == "auction":
            solver = self._auction_sharded if sharded else self._auction
            self._prewarm_neighbors(snap, route, None, features, n_groups)
            return solver(
                snap, features=features, topo_z=topo_split,
                n_groups=n_groups, tie_k=meta.tie_k,
            )
        topo_z = (
            max(topo_split) if assign_ops.needs_topo(features) else 1
        )
        if route == "wavefront":
            plan = meta.wave_plan
            if plan is None:
                # stateless/one-shot path: snap is still host-resident,
                # so the numpy partition walk is cheap here
                plan = assign_ops.plan_waves(
                    snap, features=features, wave_cap=self.wave_cap
                )
            self._prewarm_neighbors(
                snap, route, topo_z, features, n_groups,
                wave_shape=plan.members.shape, sharded=sharded,
                statics=meta.statics,
            )
            solver = self._wavefront_sharded if sharded else self._wavefront
            return solver(
                snap, wave_members=plan.members, topo_z=topo_z,
                features=features, n_groups=n_groups, statics=meta.statics,
            )
        self._prewarm_neighbors(
            snap, route, topo_z, features, n_groups, sharded=sharded,
            statics=meta.statics,
        )
        solver = self._greedy_sharded if sharded else self._greedy
        return solver(
            snap, topo_z, features, n_groups=n_groups, statics=meta.statics
        )

    def encode_pending(
        self,
        pending: Sequence[api.Pod],
        num_pods_hint: int = 0,
        lock=None,
        reservations: Sequence[Tuple[str, api.Pod]] = (),
    ) -> Tuple[schema.Snapshot, schema.SnapshotMeta]:
        """Encode pending pods + live cluster state into a device-resident
        snapshot.  `lock` (the scheduler cache's mutex) is held across the
        encode AND the device transfer: build_from_state returns views
        aliasing live arrays that informer threads mutate, and both sides
        intern into the shared vocabularies — the reference holds the cache
        mutex for UpdateSnapshot (cache.go:185) for the same reason.
        The transfer MUST NOT alias live state: build_from_state returns
        cluster tensors as views of the ClusterState arrays, and on the
        CPU backend jax.device_put can zero-copy a numpy buffer — a later
        cache mutation would then leak into an already-"materialized"
        snapshot (observed: preemption's verify restore undoing its own
        victim removal mid-solve).  The cluster leaves are host-copied
        first (pod/constraint tables are freshly allocated every build,
        so only the cluster aliases); device_put then transfers without
        per-leaf device dispatches (jnp.array's convert path issues one
        per leaf — 49 per encode).

        reservations: (node_name, pod) pairs whose requests overlay the
        named node's usage in THIS snapshot only — nominated preemptors
        waiting to land (the filters-with-nominated-pods analogue,
        runtime/framework.go:962).  The overlay is applied to the device
        copy; live state is untouched."""
        if lock is not None:
            # the encode holds the lock a concurrent wave commit needs:
            # how long it waited for it is a span of its own
            with trace.span("sched.encode.lock_wait"):
                lock.acquire()
        try:
            t_enc = time.perf_counter()
            snap, meta = self.builder.build_from_state(
                self.state, pending, num_pods_hint=num_pods_hint
            )
            dt_enc = time.perf_counter() - t_enc
            if pending and dt_enc > 0.0:
                self.last_encode_rows_per_s = len(pending) / dt_enc
            rows, reqs, nzs = [], [], []
            for node_name, pod in reservations:
                row = self.state._rows.get(node_name)
                if row is None:
                    continue  # nominated node left the cluster
                req, nz, _ = self.builder.pod_usage(pod, self.state._r)
                rows.append(row)
                reqs.append(req)
                nzs.append(nz)
            # derive routing statics while the arrays are host-resident —
            # probing them post-transfer is a blocking readback each
            no_bound = not self.state._pods
            meta.features = assign_ops.features_of(
                snap, no_bound_pods=no_bound,
                slice_policy=self.carveout_policy,
            )
            meta.topo_split = assign_ops.required_topo_z_split(snap)
            meta.n_groups = schema.num_groups(snap)
            meta.tie_k = auction_ops.default_tie_k(snap)
            # route now, while the pod tables are host numpy: the
            # wavefront partition walk reads them, and probing a
            # device-resident snapshot is a blocking readback per array
            meta.route = self._route(
                snap, meta.features, meta.topo_split, meta.n_groups
            )
            if meta.route == "wavefront":
                meta.wave_plan = assign_ops.plan_waves(
                    snap, features=meta.features, wave_cap=self.wave_cap
                )
            # The cluster half (~98% of the bytes at scale) stays
            # device-resident across steps; only dirty rows transfer
            # (models.mirror).  The pod/constraint tables are freshly
            # allocated per batch, so device_put cannot alias live state.
            # Under a mesh the mirror is NamedSharding-resident in the
            # exact layout the sharded jits' shard_map specs expect, and
            # the pod-table transfers replicate over the mesh (_put) —
            # per-batch host→device traffic stays O(changed rows) in
            # both layouts.
            if self.use_mirror:
                with trace.span("sched.encode.stage") as sp:
                    snap = self._stage(snap, meta, no_bound, sp)
            else:
                # DeviceClusterMirror gate off: full host copy +
                # transfer every step (the pre-mirror behavior — the
                # rollback knob the gate exists for).  Mesh mode keeps
                # the copies host-side and lets shard_map own placement.
                snap = snap._replace(
                    cluster=jax.tree.map(np.array, snap.cluster)
                )
                snap = jax.device_put(snap) if self.mesh is None else snap
        finally:
            if lock is not None:
                lock.release()
        if rows:
            idx = jnp.asarray(np.array(rows, dtype=np.int32))
            cluster = snap.cluster._replace(
                requested=snap.cluster.requested.at[idx].add(
                    jnp.asarray(np.stack(reqs))
                ),
                nonzero_requested=snap.cluster.nonzero_requested.at[idx].add(
                    jnp.asarray(np.stack(nzs))
                ),
            )
            snap = snap._replace(cluster=cluster)
        return snap, meta

    def _stage(
        self, snap: schema.Snapshot, meta: schema.SnapshotMeta,
        no_bound: bool, sp,
    ) -> schema.Snapshot:
        """The encode's staging: bring the resident mirror and partials
        to the state's current generation and put the pod tables on the
        device (docs/scheduler_loop.md, "The encode's staging").

        Where the mirror's sync is a scatter of at most ROW_CHUNK usage
        rows and the partials' a refresh of at most ROW_CHUNK dirty rows
        and a gather — what the encode observes, no knob — all of it
        rides the pod tables' packed buffer: ONE transfer and ONE staging
        program.  Everything else (a full upload, dirty static rows, more
        rows, a resize, first-seen classes, a periodic recompute, an
        injected fault, a mesh) takes mirror.sync() and
        PartialsCache.prepare() as they are, and the staging program then
        only gathers and unpacks; a batch without warm statics (the
        auction's route, a declined or failed sync, the partials gate
        off) takes the plain unpack.  Fault points fire in the same order
        on both paths.  `sp` (span sched.encode.stage) gets n =
        transfers, a0 = programs, a1 = 1 where the staging program
        carried the cycle's state."""
        mirror, partials = self._mirror, self._partials
        issued = _Issued()
        counters = [issued, mirror] + ([partials] if partials else [])

        def count():
            return (sum(c.transfers for c in counters),
                    sum(c.programs for c in counters))

        transfers0, programs0 = count()
        statics = None
        rows = PartialsCache.ROW_CHUNK
        warm = partials is not None and meta.route in ("greedy", "wavefront")
        # the mirror's plan fires no fault point: where it declines,
        # sync() runs first, as before, so mirror.grow still fires before
        # solve.partials
        mplan = mirror.plan_rows(rows) if warm else None
        if mplan is None:
            dev_cluster = mirror.sync()
            epochs.audit_mirror(mirror, self.state)
        plan = dirty = None
        if warm:
            # warm-start statics for the greedy-family routes: the cache
            # is an optimization layer, so any failure inside it
            # (including injected solve.partials faults) falls back to
            # the cold in-program class_statics path and invalidates its
            # residents
            try:
                plan = partials.plan(
                    snap, meta, int(snap.cluster.allocatable.shape[0])
                )
            except Exception:  # noqa: BLE001 — cold solve instead
                self._partials_failed(partials)
        if mplan is not None and plan is not None:
            dirty = partials.staged_rows(plan, rows)
        if dirty is not None:
            host, usage_idx = mplan
            cluster = mirror.resident()
            stage = _Stage(
                mirror.stage_rows(host, usage_idx, rows)
                + (pad_rows(dirty, plan.n, rows),
                   partials.slot_array(plan)),
                cluster, *partials.residents(),
            )
        else:
            if mplan is not None:
                dev_cluster = mirror.sync()  # no resize: no fault point
                epochs.audit_mirror(mirror, self.state)
            slots = None
            if plan is not None:
                try:
                    slots = partials.prepare(
                        dev_cluster, snap, plan,
                        cluster_epoch=mirror.epoch(),
                    )
                except Exception:  # noqa: BLE001 — cold solve instead
                    self._partials_failed(partials)
            cluster = dev_cluster
            stage = None
            if slots is not None and self.mesh is not None:
                # the residents are sharded: the cache's own pinned
                # gather, and the plain unpack
                statics = partials.gather(slots, plan)
            elif slots is not None:
                # the staging program only gathers and unpacks: its row
                # segments are empty (every index past the last row)
                none = np.zeros(0, dtype=np.int32)
                stage = _Stage(
                    mirror.stage_rows(self.state.tensors(), none, rows)
                    + (pad_rows(none, plan.n, rows), slots),
                    cluster, *partials.residents(),
                )
        snap = _device_fill_shortcut(
            snap._replace(cluster=cluster), self._fill_cache,
            no_bound_pods=no_bound, features=meta.features, put=self._put,
            issued=issued,
        )
        if stage is None:
            snap = _packed_device_put(
                snap, self._unpack_cache, put=self._put, issued=issued
            )
        else:
            snap, (usage, store, statics) = _packed_device_put(
                snap, self._unpack_cache, put=self._put, stage=stage,
                issued=issued,
            )
            if dirty is not None:
                mirror.adopt_rows(usage, mplan[1].shape[0])
                epochs.audit_mirror(mirror, self.state)
                partials.adopt(plan, dirty, store, mirror.epoch())
                snap = snap._replace(cluster=mirror.resident())
        if statics is not None:
            meta.statics = statics
            epochs.audit_partials(partials, self.state)
            meta.coherence_stamp = (mirror.epoch(), partials.epoch())
        transfers1, programs1 = count()
        sp.n, sp.a0 = transfers1 - transfers0, programs1 - programs0
        sp.a1 = 1.0 if dirty is not None else 0.0
        return snap

    def _partials_failed(self, partials: PartialsCache) -> None:
        partials.invalidate()  # graftlint: disable=coherence -- partials-only fault: the mirror synced cleanly above and is not a suspect
        _log.exception(
            "partials sync failed on %s; cold solve for this batch",
            device_label(),
        )

    @hot_path
    def solve_encoded_async(
        self, snap: schema.Snapshot, meta: schema.SnapshotMeta
    ) -> DeviceSolve:
        """Dispatch a prebuilt snapshot; the result stays a device future
        (DeviceSolve) and the readback happens on first names()/reasons()
        access — callers overlap it with host work."""
        act = faults.fire("batch.solve", pods=meta.num_pods)
        if (
            meta.features is not None
            and getattr(meta.features, "slices", False)
            and (meta.n_groups or 0) > 0
        ):
            # the gang carve-out dispatch point (chaos seeds 600-604):
            # fail-grade schedules kill the solve here and ride the same
            # retry/breaker containment as batch.solve faults
            faults.fire("solve.carveout", gangs=meta.n_groups)
        slot = self.arbiter
        if slot is not None:
            # multi-lane admission: at most `depth` device programs in
            # flight across every profile lane sharing this device
            slot.acquire()  # graftlint: disable=purity -- lane admission gate BEFORE dispatch, never between dispatch and readback; single-lane configs pass arbiter=None and skip it
        try:
            result = self._dispatch(snap, meta)
        except BaseException:
            if slot is not None:
                slot.release()
            raise
        if act == faults.CORRUPT and getattr(result, "scores", None) is not None:
            # injected device corruption: poison the score tensor so the
            # decode-side health check (SolveUnhealthy) trips
            result = result._replace(
                scores=jnp.full_like(result.scores, jnp.nan)
            )
        self.last_result = result
        ds = DeviceSolve(result, meta)
        ds._slot = slot
        return ds

    def solve_encoded(
        self, snap: schema.Snapshot, meta: schema.SnapshotMeta
    ) -> List[Optional[str]]:
        """Dispatch a prebuilt snapshot and decode node names (blocking)."""
        return self.solve_encoded_async(snap, meta).names()

    def schedule_pending_async(
        self,
        pending: Sequence[api.Pod],
        num_pods_hint: int = 0,
        lock=None,
        reservations: Sequence[Tuple[str, api.Pod]] = (),
    ) -> Optional[DeviceSolve]:
        """Encode + dispatch one batch without blocking on the device.
        Returns None for an empty batch.  The caller finishes the step
        with finalize_pending() once it wants the names — anything it
        does in between overlaps the device solve and the readback (the
        scheduling loop does nothing in between: it finishes in place)."""
        if not pending:
            return None
        if not self.breaker.allow_device():
            # breaker open: the device path is sick; solve on the host
            # (throughput stays > 0 while the cooldown runs)
            return self._host_fallback(
                pending, lock=lock, reservations=reservations
            )
        with trace.span("sched.encode", len(pending)) as sp_enc:
            snap, meta = self.encode_pending(
                pending, num_pods_hint=num_pods_hint, lock=lock,
                reservations=reservations,
            )
            sp_enc.a0 = trace.ROUTE_ID.get(meta.route, -1)
        try:
            with trace.span("sched.dispatch", len(pending)) as sp_run:
                sp_run.a0 = int(snap.pods.req.shape[0])
                sp_run.a1 = int(snap.cluster.allocatable.shape[0])
                ds = self.solve_encoded_async(snap, meta)
        except Exception:  # noqa: BLE001 — device dispatch/compile fault
            _log.exception(
                "device solve dispatch failed on %s [%s]; retrying once",
                device_label(), _executable_key(meta, snap),
            )
            try:
                ds = self.solve_encoded_async(snap, meta)
            except Exception:  # noqa: BLE001
                self.breaker.record_failure()
                # resident partials AND the resident mirror are fault
                # suspects here, exactly as on finalize_pending's heal
                # wire: a dispatch-time fault can be a poisoned resident
                # surfacing at trace time, and the host fallback below
                # doesn't read either — dropping both also frees their
                # HBM while the breaker cools down (graftcoh finding:
                # this site invalidated only the partials)
                with lock if lock is not None else contextlib.nullcontext():
                    if self._partials is not None:
                        self._partials.invalidate()
                    if self.use_mirror:
                        self._mirror.invalidate()
                _log.exception(
                    "device solve retry failed on %s [%s]; breaker open, "
                    "host fallback", device_label(),
                    _executable_key(meta, snap),
                )
                return self._host_fallback(
                    pending, lock=lock, reservations=reservations
                )
        # last_timings is a view of the cycle's spans: every figure
        # below comes from the clock reads that opened and closed them
        ds.encode_s = sp_enc.t1 - sp_enc.t0
        # trace/compile + dispatch-enqueue wall: on a first-of-a-bucket
        # batch this IS the XLA compile (jit blocks until the executable
        # exists); steady-state it is ~0 — the split that separates
        # compile churn from real solve regressions
        ds.dispatch_s = sp_run.t1 - sp_run.t0
        ds.dispatched_at = sp_run.t1
        return ds

    def finalize_pending(
        self,
        pending: Sequence[api.Pod],
        ds: Optional[DeviceSolve],
        lock=None,
        reservations: Sequence[Tuple[str, api.Pod]] = (),
    ) -> List[Optional[str]]:
        """Decode a dispatched batch (one coalesced readback), record the
        encode/solve/decode wall split, and run the gang admission retry
        if the batch needs it.

        Device faults surfacing at decode time (XLA runtime errors in
        device_get, the SolveUnhealthy non-finite check) retry the solve
        once; a second failure trips the circuit breaker and this batch
        — like every batch until the cooldown's half-open probe — solves
        on the host fallback instead."""
        if ds is None:
            return []
        try:
            names = ds.names()
            if not isinstance(ds, HostSolve):
                self.breaker.record_success()
        except Exception:  # noqa: BLE001 — device readback fault
            _log.exception(
                "device solve readback failed on %s [%s]; retrying once",
                device_label(), ds.executable_key(),
            )
            try:
                # resident partials AND the resident mirror are fault
                # suspects (a poisoned store/grow surfaces exactly here,
                # as SolveUnhealthy NaN scores): drop both so the
                # retry's encode performs a full recompute / full
                # (RESHARDED) re-upload — the parity gate's recovery
                # wire (solve.partials and mirror.grow CORRUPT grades)
                with lock if lock is not None else contextlib.nullcontext():
                    if self._partials is not None:
                        self._partials.invalidate()
                    if self.use_mirror:
                        self._mirror.invalidate()
                snap, meta = self.encode_pending(
                    pending, lock=lock, reservations=reservations
                )
                ds = self.solve_encoded_async(snap, meta)
                names = ds.names()
                self.breaker.record_success()
            except Exception:  # noqa: BLE001
                self.breaker.record_failure()
                _log.exception(
                    "device solve retry failed on %s [%s]; breaker open, "
                    "host fallback", device_label(), ds.executable_key(),
                )
                ds = self._host_fallback(
                    pending, lock=lock, reservations=reservations
                )
                names = ds.names()
        # the EFFECTIVE solve for this batch (retry or fallback may have
        # replaced the caller's handle): telemetry readers (wave counts,
        # reason tensors) must touch this one, not the sick original
        self.last_solve = ds
        self.last_timings = {
            "encode_s": getattr(ds, "encode_s", 0.0),
            "compile_s": getattr(ds, "dispatch_s", 0.0),
            "solve_s": ds.deferred_s + ds.decode_wait_s,
            "decode_wait_s": ds.decode_wait_s,
            "decode_overlap_s": ds.deferred_s,
        }
        return self._gang_admission_retry(
            pending, names,
            # the full batch's padded bucket as the hint: without it every
            # binary-search subset size landed in a fresh pad bucket and
            # recompiled on the hot path
            lambda subset: self.schedule_pending_no_retry(
                subset, lock=lock, reservations=reservations,
                num_pods_hint=len(pending),
            ),
        )

    def schedule_pending(
        self,
        pending: Sequence[api.Pod],
        num_pods_hint: int = 0,
        lock=None,
        reservations: Sequence[Tuple[str, api.Pod]] = (),
    ) -> List[Optional[str]]:
        """One batched scheduling step against the incremental state.
        Returns one node name (or None) per pending pod.  Placements are
        NOT auto-assumed — the host scheduler assumes/binds explicitly."""
        ds = self.schedule_pending_async(
            pending, num_pods_hint=num_pods_hint, lock=lock,
            reservations=reservations,
        )
        return self.finalize_pending(
            pending, ds, lock=lock, reservations=reservations
        )

    def schedule_pending_no_retry(
        self, pending, lock=None, reservations=(), num_pods_hint: int = 0
    ) -> List[Optional[str]]:
        if not self.breaker.allow_device():
            return self._host_fallback(
                pending, lock=lock, reservations=reservations
            ).names()
        snap, meta = self.encode_pending(
            pending, lock=lock, reservations=reservations,
            num_pods_hint=num_pods_hint,
        )
        return self.solve_encoded(snap, meta)

    # -- degraded mode (the circuit breaker's fallback) --------------------

    def _host_fallback(
        self,
        pending: Sequence[api.Pod],
        lock=None,
        reservations: Sequence[Tuple[str, api.Pod]] = (),
    ) -> HostSolve:
        """Solve one batch on the host: the per-pod exact-evaluation path
        (testing.oracle.Oracle — the independent reference-semantics
        reimplementation the parity suite validates the kernels against)
        over the retained node/pod objects, with the device post-pass's
        gang all-or-nothing mirrored host-side.

        On healthy snapshots with default plugin weights the oracle IS
        scan-parity-identical (tests/test_assign_parity.py), so a tripped
        breaker degrades throughput, not placement quality.  Nominated
        reservations are accounted as bound pods on their nominated
        nodes — a slight over-reservation (ports/labels count too) that
        errs schedulable-pods-safe."""
        from ..testing.oracle import Oracle

        sp = trace.span("sched.encode", len(pending))
        sp.a0 = trace.ROUTE_ID["host"]
        with sp, lock if lock is not None else contextlib.nullcontext():
            state = self.state
            nodes = [
                state._node_objs[name]
                for name in state._rows
                if name in state._node_objs
            ]
            oracle = Oracle(
                nodes, fit_strategy=self.score_config.fit_strategy,
                slice_policy=self.carveout_policy,
            )
            by_name = {s.node.meta.name: s for s in oracle.states}
            for key, pod in state._pods.items():
                ns = by_name.get(
                    state._pod_node.get(key) or pod.spec.node_name
                )
                if ns is not None:
                    ns.add_pod(pod)
            for node_name, pod in reservations:
                ns = by_name.get(node_name)
                if ns is not None:
                    ns.add_pod(pod)
            names = oracle.schedule(list(pending))
        # gang all-or-nothing post-pass (ops.assign _gang_release's host
        # mirror): an incomplete gang releases every member
        groups: Dict[str, List[int]] = {}
        for i, p in enumerate(pending):
            g = p.spec.scheduling_group
            if g:
                groups.setdefault(g, []).append(i)
        for idx in groups.values():
            if any(names[i] is None for i in idx):
                for i in idx:
                    names[i] = None
        self.breaker.record_fallback()
        self.last_result = None  # no reason tensor aligns with these names
        hs = HostSolve(names)
        hs.encode_s = sp.t1 - sp.t0
        return hs

    def _gang_admission_retry(
        self,
        pending: Sequence[api.Pod],
        names: List[Optional[str]],
        solve_subset,
    ) -> List[Optional[str]]:
        """Gang scarcity packing: when gangs are present and NONE placed
        completely (each went partial and all-or-nothing released all of
        them), admit gangs by priority until capacity runs out.

        The joint solve has no gang-knapsack stage — under scarcity,
        members of every gang interleave onto the same nodes and every
        gang comes back incomplete.  The live scheduler eventually
        self-heals through staggered backoff retries; one-shot callers
        (proto service, extender) would return zero.  The
        fix exploits monotonicity — if the k highest-priority gangs
        don't fit, k+1 don't either — so a binary search over the
        priority-ordered gang prefix finds the maximal admissible set in
        O(log G) extra solves, only on the everything-parked path."""
        groups: Dict[str, List[int]] = {}
        for i, p in enumerate(pending):
            g = p.spec.scheduling_group
            if g:
                groups.setdefault(g, []).append(i)
        if not groups:
            return names
        complete = [
            g for g, idx in groups.items()
            if all(names[i] is not None for i in idx)
        ]
        if complete:
            return names  # scarcity handled: some gang(s) landed
        # `names` belongs to the FULL solve; subset attempts below will
        # overwrite last_result, so keep the aligned one to restore on
        # the no-prefix-fits path (callers read reasons positionally)
        full_result = self.last_result
        # admission order: priority desc, then smaller gangs first
        order = sorted(
            groups,
            key=lambda g: (
                -max(pending[i].spec.priority for i in groups[g]),
                len(groups[g]),
                g,
            ),
        )
        nongang = [
            i for i, p in enumerate(pending) if not p.spec.scheduling_group
        ]

        def attempt(k: int) -> Optional[List[Optional[str]]]:
            idx = list(nongang)
            for g in order[:k]:
                idx.extend(groups[g])
            idx.sort()
            sub = [pending[i] for i in idx]
            sub_names = solve_subset(sub)
            admitted = {
                i for g in order[:k] for i in groups[g]
            }
            pos = {orig: j for j, orig in enumerate(idx)}
            if any(sub_names[pos[i]] is None for i in admitted):
                return None  # an admitted gang still doesn't fit
            out: List[Optional[str]] = [None] * len(pending)
            for orig, j in pos.items():
                out[orig] = sub_names[j]
            return out

        lo, hi, best = 0, len(order), None
        while lo < hi:
            mid = (lo + hi + 1) // 2
            got = attempt(mid)
            if got is not None:
                best, lo = got, mid
            else:
                hi = mid - 1
        if best is None:
            self.last_result = full_result  # re-align reasons with names
            return names
        # last_result belongs to the final SUBSET solve — its reasons no
        # longer align with the merged name list; unplaced pods here are
        # unadmitted gang members (REASON_GANG by construction)
        self.last_result = None
        return best

    # -- stateless (one-shot) ---------------------------------------------

    def snapshot(
        self,
        nodes: Sequence[api.Node],
        pending: Sequence[api.Pod],
        bound: Sequence[api.Pod] = (),
        num_pods_hint: int = 0,
    ) -> Tuple[schema.Snapshot, schema.SnapshotMeta]:
        return self.builder.build(
            nodes, pending, bound_pods=bound, num_pods_hint=num_pods_hint
        )

    def schedule(
        self,
        nodes: Sequence[api.Node],
        pending: Sequence[api.Pod],
        bound: Sequence[api.Pod] = (),
    ) -> List[Optional[str]]:
        if not pending:
            return []

        def solve(pods):
            # pad every gang-retry subset into the full batch's bucket so
            # the binary search reuses one executable instead of
            # compiling one per subset size
            snap, meta = self.snapshot(
                nodes, pods, bound, num_pods_hint=len(pending)
            )
            # derive the routing statics host-side while the snapshot is
            # host-resident (the stateless twin of encode_pending's
            # derivation) so the dispatch path never re-probes device
            # arrays, then decode through DeviceSolve: ONE coalesced
            # device_get instead of a bare np.asarray readback per
            # gang-retry subset solve (a graftlint purity finding —
            # each bare readback paid a blocking round-trip)
            meta.features = assign_ops.features_of(
                snap, slice_policy=self.carveout_policy
            )
            meta.topo_split = assign_ops.required_topo_z_split(snap)
            meta.n_groups = schema.num_groups(snap)
            meta.tie_k = auction_ops.default_tie_k(snap)
            meta.route = self._route(
                snap, meta.features, meta.topo_split, meta.n_groups
            )
            if meta.route == "wavefront":
                meta.wave_plan = assign_ops.plan_waves(
                    snap, features=meta.features, wave_cap=self.wave_cap
                )
            return self.solve_encoded_async(snap, meta).names()

        return self._gang_admission_retry(pending, solve(pending), solve)
