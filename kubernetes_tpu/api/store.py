"""In-memory versioned object store with watch streams — SHARDED.

The control-plane data path of the reference collapses into one process:
etcd revisions + the apiserver's generic registry + the watch cache
(storage/etcd3/store.go:106, registry/generic/registry/store.go:414,
storage/cacher/cacher.go:337-514) become a single store with a monotonic
resourceVersion, per-kind keyspaces, and fan-out watch channels serving
events from a bounded ring buffer.

Semantics kept from the reference:
  * every successful write bumps one global resourceVersion (etcd
    revision semantics: one counter across kinds);
  * optimistic concurrency: update with a stale resource_version fails
    with Conflict (GuaranteedUpdate's retry trigger);
  * list returns (items, rv) so a watch can resume from that rv
    (reflector's ListAndWatch contract, reflector.go:340) — the item
    set is a POINT-IN-TIME-CONSISTENT cut across every shard (taken
    under the publish lock; sub-waves are all-or-nothing in it);
  * watch(from_rv) replays buffered events after from_rv, then streams;
    a from_rv older than the buffer raises Expired — the client relists
    (the 410 Gone path).

Sharding (the etcd-concurrent-MVCC analogue): objects hash by
``(kind, namespace)`` into N ``_StoreShard``s, each owning its own
lock, object maps, journal + checkpoint snapshot (PR 8 semantics per
shard: CRC'd snapshot + wave-atomic journal-suffix replay), and
watch-dispatch backlog + fan-out thread.  Writes take only their
shard's lock for the expensive work (deep copies, mutation, admission,
wire encode, journal fsync); resourceVersion allocation and the
in-memory publish (map update + ring append + backlog handoff) happen
under ONE small global ``_rv_lock`` so rvs stay globally monotonic, the
event ring stays globally rv-ordered, and ``watch(from_rv)`` replay is
unchanged.  ``update_wave`` is a PER-SHARD transaction: a wave spanning
shards commits as one atomic sub-wave per shard (each journaled with
its own wave id, each fence-checked at publish), which is what lets the
scheduler's binder commit sub-waves concurrently and overlap store
fan-out with the next solve.

Lock order (fixed; the graftlint runtime tracker enforces it):
``_admission_lock`` (admission-armed writers only) -> ``shard._lock``
-> ``Store._rv_lock`` -> ``shard._dispatch_cv`` / ``Watch._mu``.
Shard locks are never nested with each other.

Threading: writes hold their shard lock and only append the committed
events to that shard's dispatch backlog (under the publish lock); each
shard's dedicated fan-out thread delivers them to per-watcher bounded
COALESCING buffers off every lock, so a slow consumer can never stall
writers.  A watcher that falls behind has its MODIFIED runs compacted
latest-wins and its ADDED+DELETED pairs annihilated; only when the
coalesced backlog itself overflows (more *distinct objects* pending
than the capacity) is the watcher marked Expired — bookmark rv + forced
relist, the 410 path — never silently terminated (the
survivable-overload replacement for the cacher's
terminate-blocked-watcher behaviour; see docs/robustness.md).

Delivery ordering with N fan-out threads: per OBJECT (and per shard)
delivery is strictly rv-monotonic — an object lives on exactly one
shard and one thread drains that shard's backlog in commit order.
Events of one kind that span namespaces on different shards may
interleave across shards while both fan-outs are in flight; cache-
diffing consumers (SharedInformer, the poll-style agents) are per-key
and relists resume from the list rv, so no consumer observes the skew.
A single-shard stream (one kind, one namespace — every existing
consumer) is totally ordered exactly as before.
"""

from __future__ import annotations

import copy
import logging
import threading
import time
import weakref
import zlib
from collections import OrderedDict, deque
from contextlib import nullcontext
from dataclasses import dataclass
from typing import (
    Any, Callable, Dict, Iterator, List, NamedTuple, Optional, Tuple,
)

from ..analysis import ledger as _ledger
from ..testing import faults
from ..utils import trace
from . import framing
from . import types as api

ADDED = "ADDED"
MODIFIED = "MODIFIED"
DELETED = "DELETED"
BOOKMARK = "BOOKMARK"

# default shard count for new stores: enough to split the hot kinds
# (Pod traffic per namespace, Node heartbeats, Lease renewals) onto
# independent locks/journals without paying thread overhead — shard
# fan-out threads start lazily, so small test stores stay cheap
DEFAULT_SHARDS = 4


class NotFound(KeyError):
    pass


class AlreadyExists(ValueError):
    pass


class Conflict(ValueError):
    """Stale resourceVersion on update/delete."""


class Expired(ValueError):
    """Watch start revision fell out of the event buffer (410 Gone)."""


class Fenced(ValueError):
    """A fenced write's leadership lease is stale: the caller was
    deposed between staging the wave and committing it.  The etcd
    analogue is a txn whose lease-ownership compare fails — the late
    wave of a dead leader must never double-bind."""


class FenceToken(NamedTuple):
    """Leadership proof threaded into ``Store.update_wave``: the wave
    commits only while `identity` still holds the named Lease at the
    same acquisition `generation` (lease_transitions when the caller
    acquired).  Minted by ``LeaderElector.fence_token()``.  With the
    sharded store the check runs per SUB-wave, under the publish lock,
    atomically with that sub-wave's commit."""

    name: str
    namespace: str
    identity: str
    generation: Optional[int] = None


@dataclass
class Event:
    type: str          # ADDED | MODIFIED | DELETED
    kind: str
    obj: Any           # committed object (immutable after publish)
    rv: int


def _key(namespace: str, name: str) -> str:
    return f"{namespace}/{name}" if namespace else name


def _shard_hash(kind: str, namespace: str) -> int:
    """Stable (kind, namespace) hash — crc32 so the shard map survives
    process restarts and interpreter hash randomization (recovery must
    route every journaled object back to the shard that owns it)."""
    return zlib.crc32(f"{kind}\x00{namespace}".encode())


# Watch._offer verdicts (read by the fan-out threads)
OFFER_OK = "ok"
OFFER_STOPPED = "stopped"
OFFER_EXPIRED = "expired"


class Watch:
    """One watch stream backed by a bounded per-watcher COALESCING
    buffer: iterate to receive events; stop() to cancel.

    Backpressure semantics (the survivable-overload contract):

      * events for DISTINCT objects queue in rv order;
      * a MODIFIED landing on a pending entry replaces it latest-wins
        (an un-consumed ADDED stays ADDED with the newest object — the
        consumer never saw the original);
      * a DELETED landing on a pending ADDED annihilates both (the
        consumer never learns the object existed);
      * a DELETED landing on a pending MODIFIED collapses to DELETED;
      * an ADDED landing on a pending DELETED (delete + recreate while
        the consumer lagged) collapses to MODIFIED with the new object —
        cache-diffing consumers (SharedInformer) synthesize the right
        local transition either way;
      * compaction always keeps the LATEST rv and re-sorts the entry to
        the back, so delivery stays strictly rv-monotonic per shard
        (and totally ordered for single-shard streams).

    With the sharded store, offers arrive from one fan-out thread per
    shard; the exactly-once dedup horizon is therefore PER SHARD
    (``_horizons``): each shard's offers are ascending in rv, so "at or
    below the shard's horizon" still means "already replayed at
    registration or already delivered".  ``_last_rv`` keeps the max
    across shards for observability and the expiry bookmark.

    Only when the number of distinct pending objects would exceed the
    capacity is the stream EXPIRED: pending events are dropped, the
    bookmark rv recorded, and iteration raises `Expired` so the consumer
    relists (the 410 path).  `stopped` is also set so poll-style
    consumers (agent, kubemark, the HTTP server) fall into their
    existing relist branch.  Consumer-initiated stop() ends iteration
    with StopIteration instead.
    """

    GUARDED_FIELDS = {
        "_pending": "_mu",
        "_last_rv": "_mu",
        "_horizons": "_mu",
        "stopped": "_mu",
        "expired": "_mu",
        "expired_rv": "_mu",
        "coalesced": "_mu",
    }

    def __init__(self, store: "Store", capacity: int):
        self._store = store
        self._capacity = capacity
        self._mu = threading.Condition()
        # object key -> coalesced Event, insertion/compaction order ==
        # ascending rv per shard (every insert/replace carries the
        # shard's current max rv and moves to the back)
        self._pending: "OrderedDict[str, Event]" = OrderedDict()
        # per-shard dedup horizon: highest rv this shard has delivered
        # into (or compacted through) this buffer — the fan-out threads'
        # offers dedup against it, which makes the replay-at-registration
        # + async-backlog seam exactly-once per shard
        self._horizons: List[int] = [0] * store.shard_count
        # max horizon across shards (observability + expiry bookmark)
        self._last_rv = 0
        self.stopped = False
        self.expired = False
        self.expired_rv = 0     # bookmark: last consistent rv at expiry
        self.coalesced = 0      # events compacted away in this buffer

    def stop(self) -> None:
        self._store._drop_watch(self)
        with self._mu:
            self.stopped = True
            self._mu.notify_all()

    def _pin_locked(self, rv: int) -> None:
        # registration pin (caller holds _mu): the dedup horizon of
        # EVERY shard moves to the commit the registration is consistent
        # with — backlog stragglers at or below it were covered by the
        # ring replay (or predate a from-now watch)
        for i, h in enumerate(self._horizons):
            if rv > h:
                self._horizons[i] = rv
        if rv > self._last_rv:
            self._last_rv = rv

    def _offer(self, ev: Event) -> str:
        # hot path (per event per watcher): the disarmed check is one
        # module-attribute load, not a function call
        if faults._registry is not None and faults.fire("watch.offer") == faults.DROP:
            # injected overload: as if coalescing itself overflowed —
            # the watcher expires and its consumer relists
            with self._mu:
                self._expire_locked()
            return OFFER_EXPIRED
        sid = self._store._hash_index(ev.kind, ev.obj.meta.namespace)
        with self._mu:
            if self.expired:
                return OFFER_EXPIRED
            if self.stopped:
                return OFFER_STOPPED
            if ev.rv <= self._horizons[sid]:
                # already replayed at registration (or re-offered by the
                # shard backlog after a replay covered it): exactly-once
                # dedup — per shard, because each shard's offers arrive
                # in its own ascending commit order
                return OFFER_OK
            key = _key(ev.obj.meta.namespace, ev.obj.meta.name)
            cur = self._pending.get(key)
            if cur is None:
                if len(self._pending) >= self._capacity:
                    self._expire_locked()
                    return OFFER_EXPIRED
                self._pending[key] = ev
            elif cur.type == ADDED and ev.type == DELETED:
                # annihilation: the consumer never saw the object
                del self._pending[key]
                self.coalesced += 2
            else:
                typ = ev.type
                if cur.type == ADDED and ev.type == MODIFIED:
                    typ = ADDED          # still unseen: stays a create
                elif cur.type == DELETED and ev.type == ADDED:
                    typ = MODIFIED       # delete+recreate: latest-wins
                self._pending[key] = Event(typ, ev.kind, ev.obj, ev.rv)
                self._pending.move_to_end(key)
                self.coalesced += 1
            self._horizons[sid] = ev.rv
            if ev.rv > self._last_rv:
                self._last_rv = ev.rv
            self._mu.notify_all()
            return OFFER_OK

    def _offer_batch(self, events: List["Event"]) -> str:
        """Deliver a committed chunk under ONE ``_mu`` acquisition — the
        fan-out thread's batched half of the watch path.  Per-event
        semantics (fault point, per-shard horizon dedup, coalescing
        rules, capacity expiry) are identical to ``_offer``; only the
        locking is chunked: one acquire + one notify per chunk instead
        of per event."""
        armed = faults._registry is not None
        store = self._store
        with self._mu:
            for ev in events:
                if armed and faults.fire("watch.offer") == faults.DROP:
                    # injected overload: as if coalescing overflowed
                    self._expire_locked()
                    return OFFER_EXPIRED
                if self.expired:
                    return OFFER_EXPIRED
                if self.stopped:
                    return OFFER_STOPPED
                sid = store._hash_index(ev.kind, ev.obj.meta.namespace)
                if ev.rv <= self._horizons[sid]:
                    continue  # exactly-once dedup (see _offer)
                key = _key(ev.obj.meta.namespace, ev.obj.meta.name)
                cur = self._pending.get(key)
                if cur is None:
                    if len(self._pending) >= self._capacity:
                        self._expire_locked()
                        return OFFER_EXPIRED
                    self._pending[key] = ev
                elif cur.type == ADDED and ev.type == DELETED:
                    del self._pending[key]
                    self.coalesced += 2
                else:
                    typ = ev.type
                    if cur.type == ADDED and ev.type == MODIFIED:
                        typ = ADDED          # still unseen: stays a create
                    elif cur.type == DELETED and ev.type == ADDED:
                        typ = MODIFIED       # delete+recreate: latest-wins
                    self._pending[key] = Event(typ, ev.kind, ev.obj, ev.rv)
                    self._pending.move_to_end(key)
                    self.coalesced += 1
                self._horizons[sid] = ev.rv
                if ev.rv > self._last_rv:
                    self._last_rv = ev.rv
            self._mu.notify_all()
            return OFFER_OK

    def _expire_locked(self) -> None:
        if self.expired:
            return
        self.expired = True
        self.stopped = True  # poll-style consumers relist off .stopped
        self.expired_rv = self._last_rv
        # pending events are dropped: the forced relist recovers them
        # (and everything after) from one consistent snapshot
        self._pending.clear()
        self._mu.notify_all()

    def depth(self) -> int:
        with self._mu:
            return len(self._pending)

    def __iter__(self) -> Iterator[Event]:
        return self

    def __next__(self) -> Event:
        if faults._registry is not None:
            faults.fire("watch.consume")  # injected slow consumer
        with self._mu:
            while True:
                if self._pending:
                    _, ev = self._pending.popitem(last=False)
                    return ev
                if self.expired:
                    raise Expired(
                        f"watch expired at rv {self.expired_rv}; relist"
                    )
                if self.stopped:
                    raise StopIteration
                # bounded wait: a missed notify can never park the
                # consumer forever
                self._mu.wait(0.5)

    def get(self, timeout: Optional[float] = None) -> Optional[Event]:
        """One event, or None on timeout / stream end (expiry included —
        check `.expired` / `.stopped` to distinguish and relist)."""
        if faults._registry is not None:
            faults.fire("watch.consume")  # injected slow consumer
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._mu:
            while True:
                if self._pending:
                    _, ev = self._pending.popitem(last=False)
                    return ev
                if self.stopped or self.expired:
                    return None
                if deadline is None:
                    self._mu.wait(0.5)
                    continue
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return None
                self._mu.wait(remaining)


# -- journal record codec (shared by every shard) ---------------------------


def _encode_record(rec: dict) -> str:
    """One journal line: the record JSON with a trailing crc32 over
    the crc-less serialization.  Replay re-serializes the parsed
    record (key order and value round-trips are stable under
    json.dumps) and compares — a partial page write or bit flip
    anywhere in the line fails the check even when the damage still
    parses as JSON."""
    import json

    s = json.dumps(rec)
    return '%s, "crc": %d}\n' % (s[:-1], zlib.crc32(s.encode()))


def _record_crc_ok(rec: dict, crc) -> bool:
    import json

    if crc is None:
        return True  # pre-CRC journal line: accept (upgrade path)
    return zlib.crc32(json.dumps(rec).encode()) == crc


def _fsync_dir(path: str) -> None:
    """fsync the directory holding `path` so a rename into it is
    itself durable."""
    import os

    try:
        dfd = os.open(os.path.dirname(os.path.abspath(path)), os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)
    except OSError:
        pass  # platform without directory fsync


class _StoreShard:
    """One shard of the store: its own lock, object maps, journal +
    checkpoint snapshot, and watch-dispatch backlog/thread.

    The shard owns every EXPENSIVE half of the write path — deep
    copies, mutation, admission output, wire encode, journal append +
    fsync, checkpoint I/O — so shards commit concurrently; only the
    tiny publish step (rv allocation + map update + ring/backlog
    append) serializes through the facade's ``_rv_lock``.  Recovery is
    per shard: load the shard's CRC'd snapshot, replay its journal
    suffix with PR 8 wave atomicity (a torn final wave is dropped
    whole), exactly the single-store contract scaled down to one
    shard's keyspace.
    """

    # graftlint guarded-by declarations: object maps and all journal /
    # checkpoint state share the shard mutex; the fan-out backlog has
    # its own condition (publishers append under Store._rv_lock ->
    # _dispatch_cv, the dispatcher pops under _dispatch_cv alone — one
    # lock-order direction, never a cycle)
    GUARDED_FIELDS = {
        "_objects": "_lock",
        "_versions": "_lock",
        "_last_rv": "_lock",
        "_journal": "_lock",
        "_journal_records": "_lock",
        "_journal_dirty": "_lock",
        "_journal_flushed_at": "_lock",
        "_snapshot_rv": "_lock",
        "_wave_seq": "_lock",
        "_last_checkpoint": "_lock",
        "checkpoints_total": "_lock",
        "snapshot_fallbacks": "_lock",
        "snapshot_records": "_lock",
        "journal_suffix_records": "_lock",
        "journal_recovered_records": "_lock",
        "journal_tail_truncations": "_lock",
        "journal_write_errors": "_lock",
        "journal_torn_waves": "_lock",
        "journal_frames": "_lock",
        "journal_frame_bytes": "_lock",
        "_dispatch_backlog": "_dispatch_cv",
        "_dispatch_inflight": "_dispatch_cv",
        "_dispatch_thread": "_dispatch_cv",
    }
    # reviewed lock-free: recovery runs from Store.__init__ before the
    # store is shared; the rest document "caller holds the shard lock"
    LOCKED_METHODS = frozenset({
        "_recover",
        "_replay_journal",
        "_load_snapshot",
        "_open_journal",
        "_flush_journal",
        "_journal_commit",
        "_append_journal",
        "_append_journal_wave",
    })

    def __init__(
        self,
        index: int,
        journal_path: Optional[str],
        snapshot_path: Optional[str],
        journal_sync: str,
        checkpoint_records: Optional[int],
        checkpoint_interval_seconds: float,
        journal_framing: bool = True,
    ):
        self.index = index
        self._lock = threading.RLock()
        self._objects: Dict[str, Dict[str, Any]] = {}   # kind -> key -> obj
        self._versions: Dict[str, Dict[str, int]] = {}  # kind -> key -> rv
        # highest rv this shard has committed (snapshot header rv; the
        # facade's recovered _rv is the max across shards)
        self._last_rv = 0
        # fan-out backlog: publishers append committed event batches
        # under the publish lock; this shard's dispatch thread (started
        # lazily with the first delivery, weakly referenced so abandoned
        # stores don't leak pollers) delivers them to the coalescing
        # buffers OFF every lock
        self._dispatch_cv = threading.Condition()
        self._dispatch_backlog: deque = deque()
        self._dispatch_inflight = False
        self._dispatch_thread: Optional[threading.Thread] = None
        self._journal = None
        self._journal_path = journal_path
        self._journal_sync = journal_sync
        self._journal_records = 0
        self._journal_dirty = False
        self._journal_flushed_at = time.monotonic()
        # journal health/recovery counters (the facade sums them across
        # shards; surfaced as scheduler_journal_recovered_records etc.):
        #   recovered — corrupt records replay survived;
        #   tail truncations — torn final appends cut back;
        #   write errors — appends/flushes contained (durability
        #       degraded, store keeps serving).
        self.journal_recovered_records = 0
        self.journal_tail_truncations = 0
        self.journal_write_errors = 0
        self.journal_torn_waves = 0
        # sub-wave frame mode (api/framing.py): one line + one CRC pass
        # per commit sub-wave; off reproduces the legacy per-line wave
        # format (which replay accepts forever — upgrade path)
        self._journal_framing = journal_framing
        self.journal_frames = 0
        self.journal_frame_bytes = 0
        # checkpoint / recovery state (docs/robustness.md recovery
        # contract): the snapshot sits next to the shard's journal;
        # recovery loads it and replays only the journal suffix past
        # its rv.
        self._snapshot_path = snapshot_path
        self._snapshot_rv = 0       # rv the current snapshot covers
        self._wave_seq = 0          # update_wave journal grouping id
        self._checkpoint_records = checkpoint_records
        self._checkpoint_interval = checkpoint_interval_seconds
        self._last_checkpoint = time.monotonic()
        self.checkpoints_total = 0
        self.snapshot_fallbacks = 0
        self.snapshot_records = 0
        self.journal_suffix_records = 0

    # -- recovery (runs from Store.__init__, pre-sharing) ------------------

    def _recover(self) -> None:
        """Load snapshot + replay the journal suffix + open the journal
        for append; checkpoints immediately when the replayed suffix
        dwarfs the live set (the etcd-compaction analogue)."""
        path = self._journal_path
        if path is None:
            return
        snap_n = self._load_snapshot()
        applied, lines = self._replay_journal(path, min_rv=self._snapshot_rv)
        self.snapshot_records = snap_n or 0
        self.journal_suffix_records = applied
        live = sum(len(objs) for objs in self._objects.values())
        self._journal = open(path, "a")
        self._journal_records = lines
        if lines > max(1024, 4 * live):
            # replay-time bound: a journal whose suffix dwarfs the
            # live set (churny writers — lease renewals every few
            # seconds) is checkpointed right away, so the NEXT
            # restart pays snapshot + near-empty suffix instead of
            # replaying history
            try:
                self._checkpoint_locked()
            except Exception:  # noqa: BLE001 — durability degradation
                self.journal_write_errors += 1
                logging.getLogger(__name__).exception(
                    "post-recovery checkpoint failed; journal kept"
                )

    def _open_journal(self) -> None:
        if self._journal_path is not None and self._journal is None:
            self._journal = open(self._journal_path, "a")

    def _replay_journal(
        self, path: str, min_rv: int = 0
    ) -> Tuple[int, int]:
        """Replay the shard journal; records at or below `min_rv`
        (covered by the loaded snapshot) are skipped.  update_wave
        records carry a wave id and a terminator: a wave is buffered and
        applied only when its terminator arrives, so a torn final wave
        is dropped WHOLE (truncated like a torn tail — it was never
        acknowledged durable) and a wave holed by mid-file corruption is
        skipped whole, never half-applied.  Returns (applied, good_lines)."""
        import json
        import os

        from . import wire

        if not os.path.exists(path):
            return 0, 0
        replayed = 0
        lines = 0
        good_offset = 0
        size = os.path.getsize(path)
        # wave buffering: (op, rv, kind, key, obj) per pending record
        pending: List[tuple] = []
        pending_wid = None
        pending_offset = 0       # byte offset where the pending wave began
        dead_waves: set = set()  # wave ids dropped by corruption holes

        def apply(op, rv, kind, key, obj) -> None:
            nonlocal replayed
            objs = self._objects.setdefault(kind, {})
            vers = self._versions.setdefault(kind, {})
            if op == DELETED:
                objs.pop(key, None)
                vers.pop(key, None)
            else:
                objs[key] = obj
                vers[key] = rv
            self._last_rv = max(self._last_rv, rv)
            replayed += 1

        def drop_pending(why: str) -> None:
            nonlocal pending, pending_wid
            if pending:
                self.journal_torn_waves += 1
                logging.getLogger(__name__).error(
                    "journal %s: dropping incomplete wave %s whole "
                    "(%d records; %s)", path, pending_wid, len(pending),
                    why,
                )
            if pending_wid is not None:
                dead_waves.add(pending_wid)
            pending, pending_wid = [], None

        with open(path, "rb") as f:
            for raw in f:
                line = raw.decode(errors="replace").strip()
                if not line:
                    good_offset += len(raw)
                    continue
                try:
                    rec = json.loads(line)
                    if not isinstance(rec, dict):
                        raise ValueError("journal record is not an object")
                    crc = rec.pop("crc", None)
                    if framing.is_frame(rec):
                        # one-line sub-wave frame (api/framing.py): its
                        # single CRC covers every record, its crc is
                        # MANDATORY (no pre-CRC frames exist), and the
                        # whole frame decodes up front so structural
                        # damage anywhere inside drops it atomically
                        try:
                            if not framing.frame_crc_ok(rec, crc):
                                raise ValueError("journal frame crc mismatch")
                            frame = [
                                (
                                    sub["op"], sub["rv"], sub["kind"],
                                    sub["key"],
                                    None if sub["op"] == DELETED
                                    else wire.from_wire(sub["obj"]),
                                )
                                for sub in rec["recs"]
                            ]
                        except (ValueError, KeyError, TypeError):
                            # unlike a plain corrupt line we KNOW this
                            # was a wave — count it as one
                            self.journal_torn_waves += 1
                            raise
                        op = rv = kind = key = obj = None
                    else:
                        frame = None
                        if not _record_crc_ok(rec, crc):
                            raise ValueError("journal record crc mismatch")
                        op, rv, kind = rec["op"], rec["rv"], rec["kind"]
                        key = rec["key"]
                        obj = (
                            None if op == DELETED
                            else wire.from_wire(rec["obj"])
                        )
                except (json.JSONDecodeError, ValueError, KeyError, TypeError):
                    # undecodable, CRC-failing, OR structurally-corrupt
                    # record (a line that parses as JSON but lost its
                    # fields or its object payload aborts replay just as
                    # hard as a torn one)
                    self.journal_recovered_records += 1
                    if good_offset + len(raw) >= size:
                        # corrupt TAIL (the first corrupt record with
                        # nothing valid after it): the process died
                        # mid-append; the record was never acknowledged
                        # durable — stop replay and truncate so appends
                        # continue from the last good line.  A wave the
                        # torn record belonged to is dropped whole: the
                        # truncation point backs up to the wave's start.
                        self.journal_tail_truncations += 1
                        cut = (
                            pending_offset if pending else good_offset
                        )
                        drop_pending("torn tail inside the wave")
                        with open(path, "r+b") as t:
                            t.truncate(cut)
                        break
                    # mid-file corruption (partial page write): records
                    # AFTER it were acknowledged durable — skip the bad
                    # line, keep replaying, do NOT truncate them away.
                    # A wave holed by the corruption loses its atomicity
                    # guarantee, so the whole wave is dropped instead.
                    drop_pending("mid-file corruption inside the wave")
                    logging.getLogger(__name__).error(
                        "journal %s: corrupt record at offset %d "
                        "(not tail); skipping it and keeping later "
                        "records", path, good_offset,
                    )
                    good_offset += len(raw)
                    continue
                lines += 1
                wid = rec.get("w")
                if wid is not None:
                    self._wave_seq = max(self._wave_seq, int(wid))
                if frame is not None:
                    # the frame IS its wave: no terminator protocol, no
                    # buffering — apply atomically.  A legacy wave left
                    # open before it never terminated: atomicity wins.
                    drop_pending("unterminated wave before frame")
                    for entry in frame:
                        if entry[1] > min_rv:
                            apply(*entry)
                    good_offset += len(raw)
                    continue
                if wid is not None and wid in dead_waves:
                    good_offset += len(raw)
                    continue  # straggler of a dropped wave
                if wid is None:
                    # a plain record while a wave is open means the wave
                    # never terminated (should not happen: waves append
                    # contiguously under the lock) — atomicity wins
                    drop_pending("unterminated wave before plain record")
                    if rv > min_rv:
                        apply(op, rv, kind, key, obj)
                else:
                    if pending_wid is not None and wid != pending_wid:
                        drop_pending("unterminated wave before next wave")
                    if not pending:
                        pending_offset = good_offset
                    pending_wid = wid
                    if rv > min_rv:
                        pending.append((op, rv, kind, key, obj))
                    if rec.get("wz"):
                        # terminator: the whole wave is on disk — commit
                        for entry in pending:
                            apply(*entry)
                        pending, pending_wid = [], None
                good_offset += len(raw)
            else:
                if pending:
                    # EOF with an open wave: the terminator never made
                    # it to disk — drop the wave whole and truncate so
                    # appends continue from before it
                    drop_pending("torn final wave (no terminator)")
                    self.journal_tail_truncations += 1
                    with open(path, "r+b") as t:
                        t.truncate(pending_offset)
        return replayed, lines

    def _load_snapshot(self) -> Optional[int]:
        """Load the checkpoint snapshot into empty object maps; returns
        the record count, or None when the snapshot is absent OR corrupt
        (any CRC/parse failure, a record-count mismatch against the
        header, a missing header).  Corruption rolls the maps back to
        empty and counts `snapshot_fallbacks` — the caller falls back to
        replaying the full journal, so a damaged snapshot degrades
        recovery time, never correctness.  Runs from __init__ before the
        store is shared."""
        import json
        import os

        from . import wire

        path = self._snapshot_path
        if path is None or not os.path.exists(path):
            return None
        objects: Dict[str, Dict[str, Any]] = {}
        versions: Dict[str, Dict[str, int]] = {}
        header = None
        n = 0
        max_rv = 0
        try:
            with open(path, "rb") as f:
                for raw in f:
                    line = raw.decode(errors="replace").strip()
                    if not line:
                        continue
                    rec = json.loads(line)
                    if not isinstance(rec, dict):
                        raise ValueError("snapshot record is not an object")
                    crc = rec.pop("crc", None)
                    if not _record_crc_ok(rec, crc):
                        raise ValueError("snapshot record crc mismatch")
                    if header is None:
                        if "snapshot_rv" not in rec:
                            raise ValueError("snapshot header missing")
                        header = rec
                        continue
                    rv, kind, key = rec["rv"], rec["kind"], rec["key"]
                    obj = wire.from_wire(rec["obj"])
                    objects.setdefault(kind, {})[key] = obj
                    versions.setdefault(kind, {})[key] = rv
                    max_rv = max(max_rv, rv)
                    n += 1
            if header is None or n != header["records"]:
                raise ValueError(
                    f"snapshot truncated: {n} records, header says "
                    f"{header['records'] if header else '?'}"
                )
        except Exception:  # noqa: BLE001 — recovery containment
            self.snapshot_fallbacks += 1
            logging.getLogger(__name__).exception(
                "snapshot %s corrupt; falling back to full journal "
                "replay", path,
            )
            return None
        self._objects = objects
        self._versions = versions
        self._last_rv = max(int(header["snapshot_rv"]), max_rv)
        self._snapshot_rv = int(header["snapshot_rv"])
        return n

    # -- checkpoint --------------------------------------------------------

    def _checkpoint_locked(self, truncate: bool = True) -> int:
        import os

        from . import wire

        path = self._journal_path
        if path is None or self._snapshot_path is None:
            return 0
        faults.fire("store.checkpoint", shard=self.index)
        tmp = self._snapshot_path + ".tmp"
        n = sum(len(objs) for objs in self._objects.values())
        with open(tmp, "w") as f:
            f.write(_encode_record(
                {"snapshot_rv": self._last_rv, "records": n}
            ))
            for kind, objs in self._objects.items():
                for key, obj in objs.items():
                    f.write(_encode_record({
                        "op": ADDED,
                        "rv": self._versions[kind][key],
                        "kind": kind,
                        "key": key,
                        "obj": wire.to_wire(obj),
                    }))
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self._snapshot_path)
        _fsync_dir(self._snapshot_path)
        self._snapshot_rv = self._last_rv
        self.snapshot_records = n
        self.checkpoints_total += 1
        self._last_checkpoint = time.monotonic()
        if truncate:
            # everything at or below the snapshot rv is covered by the
            # durable snapshot; the journal restarts empty
            if self._journal is not None:
                try:
                    self._journal.close()
                except (OSError, ValueError):
                    pass
            with open(path, "w") as jf:
                jf.flush()
                os.fsync(jf.fileno())
            self._journal = open(path, "a")
            self._journal_records = 0
        return n

    # -- journal (crash-only durability; caller holds the shard lock) ------

    _JOURNAL_FLUSH_S = 0.05

    def _flush_journal(self) -> None:
        faults.fire("store.journal.fsync", shard=self.index)
        self._journal.flush()

    def _journal_commit(self, lines: List[str]) -> None:
        """Write+flush journal lines with failure containment: a torn or
        failed append degrades durability (counted, logged) but never
        fails the already-committed in-memory write — the store keeps
        serving (availability over the fsync ack, unlike etcd's
        fail-stop; replay's CRC path handles whatever landed)."""
        try:
            act = faults.fire("store.journal.append", records=len(lines))
            act2 = faults.fire(
                "store.shard.journal.append",
                shard=self.index, records=len(lines),
            )
            act = act if act is not None else act2
            data = "".join(lines)
            if isinstance(act, faults.TornWrite):
                cut = max(1, int(len(data) * act.frac))
                self._journal.write(data[:cut].rstrip("\n"))
                self._journal.flush()
                raise faults.FaultInjected("torn journal append")
            self._journal.write(data)
            if self._journal_sync == "write":
                self._flush_journal()
            else:
                # group commit: one flush covers a burst of records (a
                # bind wave is thousands back-to-back); the flusher
                # thread bounds the window at _JOURNAL_FLUSH_S
                self._journal_dirty = True
                now = time.monotonic()
                if now - self._journal_flushed_at >= self._JOURNAL_FLUSH_S:
                    self._flush_journal()
                    self._journal_dirty = False
                    self._journal_flushed_at = now
        except Exception:  # noqa: BLE001 — durability degradation, not an API error
            self.journal_write_errors += 1
            logging.getLogger(__name__).exception(
                "journal append failed; continuing with degraded durability"
            )
            return
        self._journal_records += len(lines)
        live = sum(len(objs) for objs in self._objects.values())
        threshold = self._checkpoint_records or max(1024, 8 * max(live, 1))
        due = (
            self._checkpoint_interval > 0
            and time.monotonic() - self._last_checkpoint
            >= self._checkpoint_interval
        )
        if self._journal_records > threshold or due:
            try:
                self._checkpoint_locked()
            except Exception:  # noqa: BLE001
                self.journal_write_errors += 1
                logging.getLogger(__name__).exception(
                    "checkpoint failed; reopening journal for append"
                )
                if self._journal is None or self._journal.closed:
                    self._journal = open(self._journal_path, "a")

    def _append_journal(self, op: str, kind: str, key: str, obj, rv: int) -> None:
        # caller holds the shard lock; called after the publish
        if self._journal is None:
            return
        from . import wire

        t0 = trace.now()
        try:
            rec = {"op": op, "rv": rv, "kind": kind, "key": key}
            if op != DELETED:
                rec["obj"] = wire.to_wire(obj)
            self._journal_commit([_encode_record(rec)])
        finally:
            # one a write: summed by the recorder, not a row each
            trace.tally("store.journal", t0, trace.now())

    def _append_journal_wave(
        self, kind: str, records: List[Tuple[str, str, Any, int]]
    ) -> None:
        # caller holds the shard lock; one write + one flush for the
        # sub-wave.  Every record carries the shard-local wave id ("w")
        # and the last one the terminator ("wz"): replay applies the
        # wave atomically — a tail torn anywhere inside it drops the
        # WHOLE wave, so a recovered shard never holds half a bind wave.
        if self._journal is None:
            return
        from . import wire

        self._wave_seq += 1
        wid = self._wave_seq
        if self._journal_framing:
            # frame mode: ONE line, one json.dumps pass, one crc32 pass
            # for the whole sub-wave (api/framing.py) — same atomicity
            # (the frame is the wave), ~records× fewer codec calls
            recs = []
            for op, key, obj, rv in records:
                rec = {"op": op, "rv": rv, "kind": kind, "key": key}
                if op != DELETED:
                    rec["obj"] = wire.to_wire(obj)
                recs.append(rec)
            line = framing.encode_frame(wid, recs)
            if faults._registry is not None:
                action = faults.fire(
                    "journal.frame",
                    shard=self.index, wid=wid, records=len(recs),
                )
                if action is faults.CORRUPT:
                    # poison one byte in the middle of the encoded frame
                    # (trailing newline intact, so later lines survive):
                    # replay must reject the whole wave through the CRC
                    # check — torn, never half-applied.  Exercised with
                    # the native _hostplane splice AND the pure-Python
                    # fallback (the chaos parity seed).
                    mid = len(line) // 2
                    flip = "0" if line[mid] != "0" else "1"
                    line = line[:mid] + flip + line[mid + 1:]
            self.journal_frames += 1
            self.journal_frame_bytes += len(line)
            self._journal_commit([line])
            return
        lines = []
        for i, (op, key, obj, rv) in enumerate(records):
            rec = {"op": op, "rv": rv, "kind": kind, "key": key, "w": wid}
            if i == len(records) - 1:
                rec["wz"] = 1
            if op != DELETED:
                rec["obj"] = wire.to_wire(obj)
            lines.append(_encode_record(rec))
        self._journal_commit(lines)


class Store:
    """The single-process control-plane store, sharded by
    (kind, namespace) — see the module docstring for the concurrency
    contract.

    With `journal_path`, every committed write appends one JSON line to
    its SHARD's journal (``<path>`` for a 1-shard store, ``<path>.s<i>``
    otherwise) and construction replays every shard: the crash-only
    resume property whose reference counterpart is every component
    rebuilding from etcd on restart (storage/etcd3/store.go; SURVEY
    §5.4).  Replay re-applies writes without re-journaling and leaves
    the event ring empty — watchers attach after recovery and relist,
    exactly like a reflector hitting a fresh apiserver.  The shard
    count of an existing on-disk layout is inferred from the files, so
    ``Store(journal_path=...)`` restarts any layout; an EXPLICIT
    `shards` that disagrees triggers a reshard (replay old layout,
    re-route every object by the current hash, checkpoint the new
    shards, drop the old files).

    Checkpointing bounds replay PER SHARD: ``checkpoint()`` writes each
    shard's point-in-time snapshot via write-temp + fsync +
    atomic-rename and truncates that shard's journal past its
    checkpoint rv, so recovery = N × (load snapshot + replay journal
    SUFFIX), shards independently.  A corrupt snapshot falls back to
    replaying that shard's whole journal; ``update_wave`` records
    replay atomically per shard.  Recovery observability:
    ``recovery_duration_ms`` / ``snapshot_records`` /
    ``journal_suffix_records`` (summed across shards), which the
    scheduler Registry's gauges read."""

    # graftlint guarded-by declarations: the rv counter, the global
    # event ring, the watcher registry and its counters all share the
    # small publish lock (shard-owned state is annotated on _StoreShard)
    GUARDED_FIELDS = {
        "_rv": "_rv_lock",
        "_buffer": "_rv_lock",
        "_watchers": "_rv_lock",
        "watchers_terminated": "_rv_lock",
        "terminated_by_kind": "_rv_lock",
        "watch_expired_total": "_rv_lock",
        "_watch_coalesced_closed": "_rv_lock",
        "fenced_writes_total": "_rv_lock",
        "fanout_chunks": "_rv_lock",
        "fanout_chunk_events": "_rv_lock",
    }
    # reviewed lock-free / caller-holds-the-publish-lock helpers
    LOCKED_METHODS = frozenset({
        "_dispatch",
        "_dispatch_wave",
        "_queue_fanout_locked",
        "_check_fence_locked",
        "_publish_one_locked",
        "_reshard",
    })

    def __init__(
        self,
        buffer_size: int = 4096,
        # per-watcher queue matches the event buffer: a watcher that
        # can't hold buffer_size events couldn't relist-recover either,
        # and a 4k bind wave must not kill the scheduler's own informer
        watch_capacity: int = 4096,
        journal_path: Optional[str] = None,
        admission=None,
        journal_sync: str = "write",  # "write" | "interval"
        snapshot_path: Optional[str] = None,
        # journal records (post-checkpoint suffix) that trigger an
        # automatic checkpoint, PER SHARD; None = max(1024, 8 * live)
        checkpoint_records: Optional[int] = None,
        # wall-clock checkpoint cadence; 0 disables periodic checkpoints
        # (growth-triggered ones still run)
        checkpoint_interval_seconds: float = 0.0,
        # store shards (per-shard lock/journal/checkpoint/fan-out);
        # None = infer from an existing journal layout, else
        # DEFAULT_SHARDS.  1 reproduces the legacy single-lock layout
        # (journal at `journal_path` itself).
        shards: Optional[int] = None,
        # journal sub-waves as one-line frames (api/framing.py): one
        # serialization + one CRC pass per commit sub-wave.  False
        # writes the legacy per-line wave format; replay accepts BOTH,
        # interleaved, regardless of this flag (upgrade path).
        journal_framing: bool = True,
    ):
        inferred = (
            self._infer_shards(journal_path) if journal_path else None
        )
        n = shards or inferred or DEFAULT_SHARDS
        if n < 1:
            raise ValueError("shards must be >= 1")
        # the one small global rv lock: allocation + publish only — all
        # expensive write work runs under the owning shard's lock
        self._rv_lock = threading.RLock()
        self._rv = 0
        self._buffer: List[Event] = []      # global ring of recent events
        self._buffer_size = buffer_size
        self._watch_capacity = watch_capacity
        self._watchers: Dict[str, List[Watch]] = {}     # kind -> watches
        # destructive slow-watcher kills — the backpressured fan-out
        # never performs them, so churn benches assert this stays 0
        self.watchers_terminated = 0
        self.terminated_by_kind: Dict[str, int] = {}    # bounded: one key/kind
        # overload-protection observability (what the scheduler
        # Registry's scheduler_watch_* gauges read):
        #   expired — watchers converted to bookmark+relist after their
        #       coalescing buffer overflowed (or a replay overflowed);
        #   coalesced (closed) — compacted-event counts folded in from
        #       watchers that have since expired or stopped (live
        #       watchers keep their own counters; watch_stats() sums).
        self.watch_expired_total = 0
        self._watch_coalesced_closed = 0
        # update_wave sub-waves rejected because the caller's FenceToken
        # no longer matched the Lease (a deposed leader's late wave)
        self.fenced_writes_total = 0
        # batched fan-out accounting: chunks handed to watchers and the
        # events they carried (mean = fanout chunk size — what the
        # Registry's scheduler_fanout_chunk_size reads)
        self.fanout_chunks = 0
        self.fanout_chunk_events = 0
        # optional api.admission.AdmissionChain: mutate-then-validate on
        # every create/update before the commit (the apiserver admission
        # chain's position in the write path, server/config.go:983).
        # Admission-armed writes serialize on _admission_lock (held
        # through the commit) so store-reading plugins (quota validator,
        # ClusterIP allocation) stay check-then-act-safe across shards.
        self._admission = admission
        self._admission_lock = threading.RLock()
        if admission is not None and getattr(admission, "store", None) is None:
            admission.store = self  # plugin initializer (wants_store)
        self._journal_path = journal_path
        self._journal_sync = journal_sync
        # last recovery's wall time (snapshot loads + suffix replays,
        # all shards); set once at construction
        self.recovery_duration_ms = 0.0
        self._shards: List[_StoreShard] = [
            _StoreShard(
                i,
                self._shard_journal_path(journal_path, i, n),
                self._shard_snapshot_path(
                    journal_path, snapshot_path, i, n
                ),
                journal_sync,
                checkpoint_records,
                checkpoint_interval_seconds,
                journal_framing=journal_framing,
            )
            for i in range(n)
        ]
        if journal_path:
            logging.getLogger(__name__).info(
                "journal %s: %d shard(s), sync=%s, framer=%s",
                journal_path, n, journal_sync,
                "native _hostplane" if framing.native_available()
                else "python (no _hostplane extension built)",
            )
            t_rec = time.monotonic()
            if inferred is not None and shards and inferred != shards:
                # explicit shard count disagrees with the on-disk layout:
                # replay the OLD layout and re-route every object
                self._reshard(inferred, journal_path, snapshot_path)
            else:
                for shard in self._shards:
                    shard._recover()
            with self._rv_lock:
                self._rv = max(
                    [shard._last_rv for shard in self._shards] + [0]
                )
            self.recovery_duration_ms = (
                time.monotonic() - t_rec
            ) * 1000.0
            if journal_sync == "interval":
                # bounds the crash window left by batched flushing: any
                # record older than _JOURNAL_FLUSH_S is on disk
                t = threading.Thread(
                    target=self._journal_flusher,
                    name="journal-flush",
                    daemon=True,
                )
                t.start()

    # -- shard plumbing ----------------------------------------------------

    @property
    def shard_count(self) -> int:
        return len(self._shards)

    def _hash_index(self, kind: str, namespace: str) -> int:
        # raw (kind, namespace) hash — callers that accept caller-typed
        # namespaces go through shard_index() for scope normalization
        return _shard_hash(kind, namespace) % len(self._shards)

    def shard_index(self, kind: str, namespace: str = "default") -> int:
        """The shard owning (kind, namespace) — the scheduler's binder
        partitions bind waves with this so sub-waves commit per shard."""
        if kind in api.CLUSTER_SCOPED_KINDS:
            namespace = ""
        return self._hash_index(kind, namespace)

    @staticmethod
    def _shard_journal_path(
        base: Optional[str], index: int, n: int
    ) -> Optional[str]:
        if base is None:
            return None
        return base if n == 1 else f"{base}.s{index}"

    @classmethod
    def _shard_snapshot_path(
        cls,
        base: Optional[str],
        snapshot_path: Optional[str],
        index: int,
        n: int,
    ) -> Optional[str]:
        if snapshot_path is not None and n == 1:
            return snapshot_path
        jp = cls._shard_journal_path(base, index, n)
        return jp + ".snap" if jp else None

    @staticmethod
    def _infer_shards(journal_path: str) -> Optional[int]:
        """Shard count of an existing on-disk layout: ``<path>.s<i>``
        files (or their snapshots) win; a bare ``<path>``/``.snap`` is
        the 1-shard (legacy) layout; nothing on disk means no layout."""
        import glob
        import os
        import re

        found = -1
        pat = re.compile(
            re.escape(journal_path) + r"\.s(\d+)(\.snap)?$"
        )
        for p in glob.glob(glob.escape(journal_path) + ".s*"):
            m = pat.match(p)
            if m:
                found = max(found, int(m.group(1)))
        if found >= 0:
            return found + 1
        if (
            os.path.exists(journal_path)
            or os.path.exists(journal_path + ".snap")
        ):
            return 1
        return None

    def _reshard(
        self,
        old_n: int,
        journal_path: str,
        snapshot_path: Optional[str],
    ) -> None:
        """Re-route an on-disk layout of `old_n` shards into the current
        shard set: replay the old layout (full PR 8 recovery per old
        shard), hash every live object to its new shard, checkpoint the
        new shards (their journals start empty past the snapshot), then
        drop the old files.  Runs from __init__ before sharing."""
        import os

        old = [
            _StoreShard(
                i,
                self._shard_journal_path(journal_path, i, old_n),
                self._shard_snapshot_path(
                    journal_path, snapshot_path, i, old_n
                ),
                self._journal_sync,
                None,
                0.0,
            )
            for i in range(old_n)
        ]
        rv = 0
        for osh in old:
            osh._recover()
            rv = max(rv, osh._last_rv)
            for kind, objs in osh._objects.items():
                for key, obj in objs.items():
                    tgt = self._shards[
                        self._hash_index(kind, obj.meta.namespace)
                    ]
                    tgt._objects.setdefault(kind, {})[key] = obj
                    tgt._versions.setdefault(kind, {})[key] = (
                        osh._versions[kind][key]
                    )
            if osh._journal is not None:
                try:
                    osh._journal.close()
                except (OSError, ValueError):
                    pass
        old_files = []
        for osh in old:
            old_files += [osh._journal_path, osh._snapshot_path]
        for shard in self._shards:
            shard._last_rv = rv
            shard._open_journal()
            shard._checkpoint_locked(truncate=True)
        keep = set()
        for shard in self._shards:
            keep.update({shard._journal_path, shard._snapshot_path})
        for path in old_files:
            if path and path not in keep and os.path.exists(path):
                os.remove(path)

    def _journal_flusher(self) -> None:
        while True:
            time.sleep(_StoreShard._JOURNAL_FLUSH_S)
            live = False
            for shard in self._shards:
                with shard._lock:
                    if shard._journal is None:
                        continue
                    live = True
                    if shard._journal_dirty:
                        try:
                            shard._journal.flush()
                        except ValueError:  # closed mid-compaction race
                            pass
                        shard._journal_dirty = False
                        shard._journal_flushed_at = time.monotonic()
            if not live:
                return

    # -- aggregated shard counters (legacy single-store surface) -----------

    def _sum(self, field: str) -> int:
        return sum(getattr(shard, field) for shard in self._shards)

    @property
    def journal_recovered_records(self) -> int:
        return self._sum("journal_recovered_records")

    @property
    def journal_tail_truncations(self) -> int:
        return self._sum("journal_tail_truncations")

    @property
    def journal_write_errors(self) -> int:
        return self._sum("journal_write_errors")

    @property
    def journal_torn_waves(self) -> int:
        return self._sum("journal_torn_waves")

    @property
    def journal_frames(self) -> int:
        return self._sum("journal_frames")

    @property
    def journal_frame_bytes(self) -> int:
        return self._sum("journal_frame_bytes")

    @property
    def snapshot_fallbacks(self) -> int:
        return self._sum("snapshot_fallbacks")

    @property
    def checkpoints_total(self) -> int:
        return self._sum("checkpoints_total")

    @property
    def snapshot_records(self) -> int:
        return self._sum("snapshot_records")

    @property
    def journal_suffix_records(self) -> int:
        return self._sum("journal_suffix_records")

    # -- helpers -----------------------------------------------------------

    @staticmethod
    def _meta(obj: Any) -> api.ObjectMeta:
        return obj.meta

    def _kind_of(self, obj: Any) -> str:
        kind = getattr(obj, "KIND", None)
        if not kind:
            raise TypeError(f"object {obj!r} has no KIND")
        return kind

    def _write_guard(self):
        """Admission-armed writes hold the admission lock THROUGH the
        commit (check-then-act atomicity across shards — two concurrent
        creates must not both pass quota or allocate one ClusterIP);
        plain stores pay nothing."""
        if self._admission is not None:
            return self._admission_lock
        return nullcontext()

    def _dispatch(self, ev: Event) -> None:
        # caller holds the publish lock: global ring append + backlog
        # handoff to the owning shard only — the fan-out itself runs on
        # that shard's dispatch thread off every lock
        self._buffer.append(ev)
        if len(self._buffer) > self._buffer_size:
            del self._buffer[: self._buffer_size // 4]
        self._queue_fanout_locked(
            self._hash_index(ev.kind, ev.obj.meta.namespace),
            ev.kind, [ev],
        )

    def _dispatch_wave(self, kind: str, events: List[Event]) -> None:
        # caller holds the publish lock; one ring extend + ONE backlog
        # handoff for the whole sub-wave (the shard's fan-out thread
        # delivers it as a batch)
        self._buffer.extend(events)
        excess = len(self._buffer) - self._buffer_size
        if excess > 0:
            del self._buffer[: excess + self._buffer_size // 4]
        self._queue_fanout_locked(
            self._hash_index(kind, events[0].obj.meta.namespace),
            kind, events,
        )

    def _queue_fanout_locked(
        self, sid: int, kind: str, events: List[Event]
    ) -> None:
        # caller holds the publish lock.  No watchers for the kind means
        # no delivery obligation: a watcher registered later replays
        # from the ring (watch(from_rv)) or starts from-now with its
        # horizons pinned to the current rv, so skipping the backlog is
        # exact.
        if not self._watchers.get(kind):
            return
        shard = self._shards[sid]
        with shard._dispatch_cv:
            self._ensure_dispatcher_cv_held(shard)
            shard._dispatch_backlog.append((kind, events))
            shard._dispatch_cv.notify_all()

    def _ensure_dispatcher_cv_held(self, shard: _StoreShard) -> None:
        # caller holds the shard's dispatch condition.  Lazy +
        # self-healing: the thread starts with the first delivery and is
        # restarted here if an injected crash killed it (every handoff
        # passes through this check).
        t = shard._dispatch_thread
        if t is not None and t.is_alive():
            return
        t = threading.Thread(
            target=_watch_dispatch_loop,
            args=(weakref.ref(self), shard.index),
            name=f"watch-dispatch-{shard.index}",
            daemon=True,
        )
        shard._dispatch_thread = t
        t.start()

    def _fan_out(self, kind: str, events: List[Event]) -> None:
        """Deliver one committed batch to every watcher of `kind` — a
        shard dispatch thread's half of the watch path, running OFF
        every store lock so per-watcher coalescing work never blocks
        writers.  The chunk reaches each watcher through ONE
        ``Watch._mu`` acquisition (``_offer_batch``) instead of a
        per-event lock round-trip."""
        with self._rv_lock:
            watchers = list(self._watchers.get(kind, ()))
            if watchers:
                self.fanout_chunks += 1
                self.fanout_chunk_events += len(events)
        expired: List[Watch] = []
        for w in watchers:
            try:
                if w._offer_batch(events) is OFFER_EXPIRED:
                    expired.append(w)
            except Exception:  # noqa: BLE001 — per-watcher containment
                # a poisoned offer (fault-schedule exception, corrupt
                # payload) must cost only THIS watcher, and it must cost
                # it loudly: expire the stream so the consumer relists.
                # Letting the exception unwind the whole batch silently
                # starved every remaining watcher of the rest of the
                # batch with no 410 signal — a stale informer cache with
                # no recovery path (interleave scenario
                # 'writers_vs_dispatch' with a watch.offer fail schedule
                # pins this).
                logging.getLogger(__name__).exception(
                    "watch offer failed; expiring the watcher"
                )
                with w._mu:
                    w._expire_locked()
                expired.append(w)
        for w in expired:
            self._retire_expired_watch(w, kind)

    def _retire_expired_watch(self, w: Watch, kind: str) -> None:
        with self._rv_lock:
            ws = self._watchers.get(kind)
            if ws is not None and w in ws:
                ws.remove(w)
            self.watch_expired_total += 1
            with w._mu:  # _rv_lock -> Watch._mu (same order as replay)
                self._watch_coalesced_closed += w.coalesced
                w.coalesced = 0

    # -- CRUD --------------------------------------------------------------

    def create(self, obj: Any) -> Any:
        # admission, locks, publish and journal of one write: the recorder
        # sums these per thread (utils/trace.tally), not a row a write
        t0 = trace.now()
        try:
            return self._create(obj)
        finally:
            trace.tally("store.create", t0, trace.now())

    def _create(self, obj: Any) -> Any:
        with self._write_guard():
            admitted = False
            if self._admission is not None:
                # admit a server-side COPY: mutators must never edit the
                # caller's object (a rejected or conflicting write would
                # leave the caller's template silently modified — every
                # other store path deep-copies for exactly this
                # isolation).  The admission lock is held through the
                # commit, so store-reading plugins stay
                # check-then-act-safe (see _write_guard).
                obj = self._admission.admit(copy.deepcopy(obj), "CREATE")
                admitted = True
            kind = self._kind_of(obj)
            meta = self._meta(obj)
            if kind in api.CLUSTER_SCOPED_KINDS and meta.namespace:
                # resource scope normalization: cluster-scoped objects
                # live at namespace "" regardless of what the caller set
                meta.namespace = ""
            key = _key(meta.namespace, meta.name)
            shard = self._shards[self._hash_index(kind, meta.namespace)]
            with shard._lock:
                objs = shard._objects.setdefault(kind, {})
                if key in objs:
                    raise AlreadyExists(f"{kind} {key} exists")
                if not admitted:  # the admitted copy is already unaliased
                    obj = copy.deepcopy(obj)
                if not obj.meta.creation_timestamp:
                    obj.meta.creation_timestamp = time.time()
                with self._rv_lock:
                    rv = self._publish_one_locked(
                        shard, ADDED, kind, key, obj
                    )
                shard._append_journal(ADDED, kind, key, obj, rv)
                return copy.deepcopy(obj)

    def _publish_one_locked(
        self,
        shard: _StoreShard,
        op: str,
        kind: str,
        key: str,
        obj: Any,
        set_rv: bool = True,
        event_copy: bool = False,
    ) -> int:
        """The tiny global publish step (caller holds the shard lock AND
        the publish lock): allocate the rv, install/remove the object in
        the shard maps, append the event to the ring and the shard
        backlog.  The dispatched Event aliases the committed object by
        default (no defensive copy): committed objects are never mutated
        in place — an update replaces the map entry — and watch
        consumers already share one Event payload across every watcher.
        `set_rv=False` leaves the object's meta untouched (delete() of a
        STORED object: mutating its rv would break the immutability the
        lock-free list() cut depends on); `event_copy=True` deep-copies
        the event payload (paths that hand the same object back to the
        caller, who may mutate it while the fan-out is in flight)."""
        self._rv += 1
        rv = self._rv
        if set_rv:
            obj.meta.resource_version = rv
        objs = shard._objects.setdefault(kind, {})
        vers = shard._versions.setdefault(kind, {})
        if op == DELETED:
            objs.pop(key, None)
            vers.pop(key, None)
        else:
            objs[key] = obj
            vers[key] = rv
        shard._last_rv = rv
        ev_obj = copy.deepcopy(obj) if event_copy else obj
        self._dispatch(Event(op, kind, ev_obj, rv))
        return rv

    def get(self, kind: str, name: str, namespace: str = "default") -> Any:
        if kind in api.CLUSTER_SCOPED_KINDS:
            namespace = ""
        key = _key(namespace, name)
        shard = self._shards[self._hash_index(kind, namespace)]
        with shard._lock:
            try:
                return copy.deepcopy(shard._objects[kind][key])
            except KeyError:
                raise NotFound(f"{kind} {key}") from None

    def update(
        self, obj: Any, *, force: bool = False, copy_result: bool = True
    ) -> Any:
        """Optimistic-concurrency update: obj.meta.resource_version must
        match the stored version unless force (the GuaranteedUpdate retry
        loop's compare step).  copy_result=False skips the defensive
        deep copy of the return value for hot-path callers that discard
        it (the scheduler's bind wave) — the returned object is then the
        COMMITTED one and must not be mutated."""
        with self._write_guard():
            admitted = False
            if self._admission is not None:
                obj = self._admission.admit(copy.deepcopy(obj), "UPDATE")
                admitted = True
            kind = self._kind_of(obj)
            meta = self._meta(obj)
            if kind in api.CLUSTER_SCOPED_KINDS and meta.namespace:
                meta.namespace = ""
            key = _key(meta.namespace, meta.name)
            shard = self._shards[self._hash_index(kind, meta.namespace)]
            with shard._lock:
                objs = shard._objects.get(kind, {})
                if key not in objs:
                    raise NotFound(f"{kind} {key}")
                current_rv = shard._versions[kind][key]
                if not force and meta.resource_version != current_rv:
                    raise Conflict(
                        f"{kind} {key}: rv {meta.resource_version} != "
                        f"{current_rv}"
                    )
                if not admitted:
                    obj = copy.deepcopy(obj)
                if (
                    obj.meta.deletion_timestamp is not None
                    and not obj.meta.finalizers
                ):
                    # last finalizer dropped on a deleting object: the
                    # update completes the two-phase delete (store.go:1176)
                    with self._rv_lock:
                        rv = self._publish_one_locked(
                            shard, DELETED, kind, key, obj,
                            event_copy=True,  # obj is handed back below
                        )
                    shard._append_journal(DELETED, kind, key, None, rv)
                    return obj
                with self._rv_lock:
                    rv = self._publish_one_locked(
                        shard, MODIFIED, kind, key, obj
                    )
                shard._append_journal(MODIFIED, kind, key, obj, rv)
                return copy.deepcopy(obj) if copy_result else obj

    def update_wave(
        self,
        kind: str,
        updates: List[Tuple[str, str, Callable[[Any], None]]],
        *,
        admit: bool = True,
        fence: Optional[FenceToken] = None,
        shard_hint: Optional[int] = None,
    ) -> Tuple[List[str], Dict[str, Exception]]:
        """Commit a wave of read-modify-write updates as per-shard
        transactions.

        `updates` is a list of (name, namespace, mutate) where mutate(obj)
        edits a private copy of the stored object in place.  The wave is
        partitioned by shard; each SUB-wave runs under one shard-lock
        acquisition with ONE coalesced journal append (a single write +
        flush for every record of that shard) and ONE watch fan-out
        handoff — the scheduler's bind wave pays per-pod costs only for
        the copy and the mutation, not for lock/journal/dispatch.  A
        single-shard wave (one kind, one namespace — every bind sub-wave
        the scheduler commits) is exactly the PR 1 single-transaction
        contract; a wave SPANNING shards is atomic per shard, not across
        them (callers that need cross-shard atomicity — none in-tree —
        must partition with ``shard_index`` themselves).

        Failure splits per object, never per wave: a missing object, a
        mutate() exception, or an admission rejection lands in the
        returned error map under its "namespace/name" key and the rest of
        the wave commits.  Returns (applied_keys, errors).

        Each committed object still gets its own resourceVersion and its
        own watch Event, so watch/informer semantics are byte-identical
        to per-object update(); only the write-path overhead is shared.

        `fence` (a FenceToken) makes every sub-wave a LEADERSHIP-
        CONDITIONAL transaction: under the publish lock, the named Lease
        must still be held by the token's identity at the token's
        acquisition generation, or the sub-wave is rejected whole with
        `Fenced` (counted in `fenced_writes_total`) — a deposed leader's
        late bind wave can never double-bind behind its successor's back
        (the etcd lease-ownership txn compare).  The fence is also
        pre-checked before the first sub-wave so an already-stale wave
        commits nothing.

        `shard_hint` is the STREAMED HAND-OFF fast path: a caller that
        already partitioned its wave with ``shard_index`` (the binder's
        per-shard sub-waves, streamed or pooled) names the owning shard
        and the store verifies it with ONE hash per distinct namespace
        instead of re-hashing every object.  A mismatched hint (a wave
        that actually spans shards) falls back to the full partition —
        misrouted records would split ownership silently, so the hint
        is an optimization, never a trust boundary."""
        faults.fire("store.update_wave", kind=kind, updates=len(updates))
        applied: List[str] = []
        errors: Dict[str, Exception] = {}
        # partition by shard, preserving caller order within each shard
        groups: "OrderedDict[int, List[tuple]]" = OrderedDict()
        hinted = False
        if (
            shard_hint is not None
            and 0 <= shard_hint < len(self._shards)
            and updates
        ):
            hinted = True
            memo: Dict[str, int] = {}
            normalized: List[tuple] = []
            for name, namespace, mutate in updates:
                if kind in api.CLUSTER_SCOPED_KINDS:
                    namespace = ""
                sid = memo.get(namespace)
                if sid is None:
                    sid = memo[namespace] = self._hash_index(kind, namespace)
                if sid != shard_hint:
                    hinted = False
                    break
                normalized.append((name, namespace, mutate))
            if hinted:
                groups[shard_hint] = normalized
        if not hinted:
            groups.clear()
            for name, namespace, mutate in updates:
                if kind in api.CLUSTER_SCOPED_KINDS:
                    namespace = ""
                sid = self._hash_index(kind, namespace)
                groups.setdefault(sid, []).append((name, namespace, mutate))
        with self._write_guard():
            if fence is not None:
                # pre-flight: a wave staged by an already-deposed leader
                # commits NOTHING (matches the single-store contract for
                # empty and single-shard waves alike)
                with self._rv_lock:
                    self._check_fence_locked(fence)
            for sid, group in groups.items():
                a, e = self._update_subwave(
                    self._shards[sid], kind, group, admit, fence
                )
                applied.extend(a)
                errors.update(e)
        return applied, errors

    def _update_subwave(
        self,
        shard: _StoreShard,
        kind: str,
        group: List[tuple],
        admit: bool,
        fence: Optional[FenceToken],
    ) -> Tuple[List[str], Dict[str, Exception]]:
        """One shard's sub-wave: prepare (copy + mutate + admit) under
        the shard lock, publish atomically under the publish lock
        (fence-checked), then ONE journal append for the sub-wave."""
        faults.fire(
            "store.shard.update_wave",
            shard=shard.index, kind=kind, updates=len(group),
        )
        applied: List[str] = []
        errors: Dict[str, Exception] = {}
        with trace.span("store.update_wave", len(group)) as sp, shard._lock:
            sp.a0 = shard.index
            objs = shard._objects.get(kind, {})
            prepared: List[Tuple[str, Any]] = []   # (key, mutated copy)
            for name, namespace, mutate in group:
                key = _key(namespace, name)
                cur = objs.get(key)
                if cur is None:
                    errors[key] = NotFound(f"{kind} {key}")
                    continue
                obj = copy.deepcopy(cur)
                try:
                    mutate(obj)
                    if admit and self._admission is not None:
                        obj = self._admission.admit(obj, "UPDATE")
                except Exception as e:  # noqa: BLE001 — per-object split
                    errors[key] = e
                    continue
                prepared.append((key, obj))
            if not prepared:
                return applied, errors
            records: List[Tuple[str, str, Any, int]] = []
            events: List[Event] = []
            with self._rv_lock:
                if fence is not None:
                    self._check_fence_locked(fence)
                vers = shard._versions.setdefault(kind, {})
                for key, obj in prepared:
                    self._rv += 1
                    rv = self._rv
                    obj.meta.resource_version = rv
                    if (
                        obj.meta.deletion_timestamp is not None
                        and not obj.meta.finalizers
                    ):
                        # mirror update(): dropping the last finalizer on
                        # a deleting object completes the two-phase delete
                        objs.pop(key, None)
                        vers.pop(key, None)
                        records.append((DELETED, key, None, rv))
                        events.append(Event(DELETED, kind, obj, rv))
                    else:
                        objs[key] = obj
                        vers[key] = rv
                        records.append((MODIFIED, key, obj, rv))
                        events.append(Event(MODIFIED, kind, obj, rv))
                    applied.append(key)
                shard._last_rv = self._rv
                self._dispatch_wave(kind, events)
            if shard._journal is not None:
                with trace.span("store.journal", len(records)):
                    shard._append_journal_wave(kind, records)
        return applied, errors

    def _check_fence_locked(self, fence: FenceToken) -> None:
        # caller holds the publish lock — the Lease cannot change while
        # the sub-wave publishes, so the compare-and-commit is atomic
        lease_shard = self._shards[
            self._hash_index("Lease", fence.namespace)
        ]
        lease = lease_shard._objects.get("Lease", {}).get(
            _key(fence.namespace, fence.name)
        )
        spec = getattr(lease, "spec", None)
        if (
            spec is None
            or spec.holder_identity != fence.identity
            or (
                fence.generation is not None
                and spec.lease_transitions != fence.generation
            )
        ):
            self.fenced_writes_total += 1
            holder = getattr(spec, "holder_identity", None)
            raise Fenced(
                f"wave fenced: lease {fence.namespace}/"
                f"{fence.name} held by {holder!r}, caller "
                f"{fence.identity!r} gen {fence.generation}"
            )

    def delete(self, kind: str, name: str, namespace: str = "default") -> Any:
        # summed per thread like a create's (utils/trace.tally)
        t0 = trace.now()
        try:
            return self._delete(kind, name, namespace)
        finally:
            trace.tally("store.delete", t0, trace.now())

    def _delete(self, kind: str, name: str, namespace: str) -> Any:
        """Remove an object.  Objects carrying finalizers get the
        reference's two-phase deletion (registry/generic/registry/
        store.go:1116): deletionTimestamp is set and a MODIFIED event
        fires; the real removal happens when the last finalizer is
        dropped via update() — the node agent's graceful pod shutdown
        and any future finalizing controller ride this."""
        if kind in api.CLUSTER_SCOPED_KINDS:
            namespace = ""
        key = _key(namespace, name)
        shard = self._shards[self._hash_index(kind, namespace)]
        with shard._lock:
            objs = shard._objects.get(kind, {})
            if key not in objs:
                raise NotFound(f"{kind} {key}")
            obj = objs[key]
            if obj.meta.finalizers and obj.meta.deletion_timestamp is not None:
                # already terminating: delete-on-deleting is a no-op
                # (finalizers still gate the removal; a GC re-delete must
                # not hard-remove mid-grace)
                return copy.deepcopy(obj)
            if obj.meta.finalizers and obj.meta.deletion_timestamp is None:
                obj = copy.deepcopy(obj)
                obj.meta.deletion_timestamp = time.time()
                with self._rv_lock:
                    rv = self._publish_one_locked(
                        shard, MODIFIED, kind, key, obj
                    )
                shard._append_journal(MODIFIED, kind, key, obj, rv)
                return copy.deepcopy(obj)
            with self._rv_lock:
                # the STORED object: its meta stays at its committed rv
                # (set_rv=False) and the event payload is a copy — the
                # raw object is returned to the caller below
                rv = self._publish_one_locked(
                    shard, DELETED, kind, key, obj,
                    set_rv=False, event_copy=True,
                )
            shard._append_journal(DELETED, kind, key, None, rv)
            return obj

    def list(
        self,
        kind: str,
        namespace: Optional[str] = None,
        selector: Optional[Callable[[Any], bool]] = None,
    ) -> Tuple[List[Any], int]:
        """(items, resource_version) — the ListAndWatch handoff point.

        The cut is POINT-IN-TIME CONSISTENT across shards: object
        references and the rv are captured under the publish lock (all
        publishes serialize through it, so a sub-wave is all-or-nothing
        in the cut), and the defensive deep copies happen OUTSIDE the
        lock — committed objects are immutable, an update replaces the
        map entry — so the snapshot path no longer blocks writers for
        the O(items) copy cost.

        `selector` is called on the STORED objects, before any copy is
        made, and only what it accepts is copied.  It must neither
        mutate nor keep them.  A selector that accepts nothing therefore
        reads the kind by reference at no copy (the event recorder's
        resync does)."""
        if faults._registry is not None:
            # relist-storm chaos: injected list latency models a control
            # plane whose snapshot path is the contended resource
            faults.fire("store.list", kind=kind)
        with self._rv_lock:
            refs = [
                o
                for shard in self._shards
                for o in shard._objects.get(kind, {}).values()
            ]
            rv = self._rv
        items = [
            copy.deepcopy(o)
            for o in refs
            if (namespace is None or o.meta.namespace == namespace)
            and (selector is None or selector(o))
        ]
        return items, rv

    def kinds(self) -> List[str]:
        """Object kinds the store currently holds (the GC/namespace
        controllers sweep every kind, like the reference's
        RESTMapper-driven resource discovery)."""
        with self._rv_lock:
            out: List[str] = []
            for shard in self._shards:
                for k, objs in shard._objects.items():
                    if objs and k not in out:
                        out.append(k)
            return out

    # -- checkpoint --------------------------------------------------------

    def checkpoint(self, truncate: bool = True) -> int:
        """Checkpoint every shard: each writes a point-in-time snapshot
        of its live objects and (by default) truncates its journal past
        the checkpoint rv, bounding the next recovery to N × (snapshot +
        journal suffix).  Crash-safe by construction per shard
        (write-temp + fsync + atomic-rename + dir fsync; the journal is
        only truncated AFTER the snapshot is durable).  Shards
        checkpoint one at a time — a crash between shards leaves some
        shards on the old snapshot + full journal, which recovery
        handles per shard.  ``truncate=False`` keeps the journals
        (full-replay oracle mode — the chaos suite's bit-parity check).
        Returns the total snapshot record count."""
        total = 0
        for shard in self._shards:
            with shard._lock:
                total += shard._checkpoint_locked(truncate=truncate)
        return total

    # -- watch -------------------------------------------------------------

    def watch(self, kind: str, from_rv: Optional[int] = None) -> Watch:
        """Stream events for `kind` after `from_rv` (exclusive).  None
        means 'from now'.  Raises Expired when from_rv predates the event
        buffer — relist and retry (reflector.go 410 handling).  The ring
        is GLOBAL and rv-ordered (appends happen under the publish
        lock), so replay across shards is exactly the single-store
        replay."""
        with self._rv_lock:
            w = Watch(self, self._watch_capacity)
            if from_rv is not None:
                oldest_known = self._buffer[0].rv if self._buffer else self._rv + 1
                if from_rv + 1 < oldest_known and from_rv < self._rv:
                    raise Expired(
                        f"rv {from_rv} too old (buffer starts at {oldest_known})"
                    )
                for ev in self._buffer:
                    if ev.kind == kind and ev.rv > from_rv:
                        if w._offer(ev) is not OFFER_OK:
                            # the replay itself overflowed the coalescing
                            # buffer (or was fault-dropped): this stream
                            # would be lossy FROM BIRTH — refuse it; the
                            # client relists (410 path)
                            self.watch_expired_total += 1
                            raise Expired(
                                f"rv {from_rv} replay overflowed the "
                                "watch buffer; relist"
                            )
            with w._mu:
                # pin the dedup horizons to the commit the registration
                # is consistent with: backlog stragglers at or below it
                # were covered by the replay (or predate a from-now
                # watch) and must not be re-delivered
                w._pin_locked(self._rv)
            self._watchers.setdefault(kind, []).append(w)
            return w

    def _drop_watch(self, w: Watch) -> None:
        with self._rv_lock:
            for ws in self._watchers.values():
                if w in ws:
                    ws.remove(w)
                    break
            with w._mu:
                self._watch_coalesced_closed += w.coalesced
                w.coalesced = 0

    def dispatch_depth(self) -> int:
        """Committed-but-undelivered watch events queued at the shard
        fan-out threads — the store-side overload signal the adaptive
        APF controller reads (a deep backlog means watchers cannot keep
        up with the commit rate, so admission should shed)."""
        total = 0
        for shard in self._shards:
            with shard._dispatch_cv:
                total += sum(
                    len(evs) for _, evs in shard._dispatch_backlog
                )
        return total

    def watch_stats(self) -> Dict[str, int]:
        """Fan-out observability snapshot: deepest per-watcher pending
        backlog, fan-out dispatch backlog, total compacted events,
        expiries, and (legacy) destructive terminations — what the
        scheduler Registry's scheduler_watch_* gauges read, on the
        reader's thread (it takes the watchers' locks)."""
        dispatch_depth = self.dispatch_depth()
        with self._rv_lock:
            depth = 0
            coalesced = self._watch_coalesced_closed
            for ws in self._watchers.values():
                for w in ws:
                    with w._mu:
                        depth = max(depth, len(w._pending))
                        coalesced += w.coalesced
            return {
                "watch_queue_depth": depth,
                "watch_dispatch_depth": dispatch_depth,
                "watch_coalesced_total": coalesced,
                "watch_expired_total": self.watch_expired_total,
                "watchers_terminated": self.watchers_terminated,
            }

    # -- lifecycle ---------------------------------------------------------

    def close(self, timeout: float = 5.0) -> None:
        """Graceful shutdown: drain every shard's watch-dispatch backlog
        (pending committed batches reach their watchers), then flush AND
        fsync every shard journal before returning — under
        ``journal_sync="interval"`` the final dirty group-commit batch
        would otherwise sit in the userspace buffer and die with the
        process.  The store stops journaling afterwards; reads keep
        working (tests inspect closed stores)."""
        import os

        deadline = time.monotonic() + timeout
        for shard in self._shards:
            with shard._dispatch_cv:
                while (
                    (shard._dispatch_backlog or shard._dispatch_inflight)
                    and time.monotonic() < deadline
                ):
                    shard._dispatch_cv.wait(0.05)
        for shard in self._shards:
            with shard._lock:
                j, shard._journal = shard._journal, None
                shard._journal_dirty = False
            if j is not None:
                try:
                    j.flush()
                    os.fsync(j.fileno())
                    j.close()
                except (OSError, ValueError):
                    logging.getLogger(__name__).exception(
                        "journal close flush failed; tail durability "
                        "degraded"
                    )

    def state_fingerprint(self) -> Dict[str, Any]:
        """A stable, comparison-friendly serialization of the full
        committed state: store rv plus (kind, key) -> (rv, wire(obj)),
        merged across shards (shard topology is invisible — a 1-shard
        and an 8-shard store holding the same objects fingerprint
        identically).  Two stores with equal fingerprints hold
        bit-identical state — the chaos suite compares snapshot+suffix
        recovery against a full-replay oracle with this."""
        from . import wire

        with self._rv_lock:
            merged: Dict[str, Dict[str, tuple]] = {}
            for shard in self._shards:
                for kind, objs in shard._objects.items():
                    if not objs:
                        continue
                    out = merged.setdefault(kind, {})
                    for key, obj in objs.items():
                        out[key] = (
                            shard._versions[kind][key], wire.to_wire(obj)
                        )
            return {
                "rv": self._rv,
                "objects": {
                    kind: dict(sorted(entries.items()))
                    for kind, entries in sorted(merged.items())
                },
            }

    # -- convenience -------------------------------------------------------

    @property
    def resource_version(self) -> int:
        with self._rv_lock:
            return self._rv


def _watch_dispatch_loop(store_ref: "weakref.ref[Store]", sid: int) -> None:
    """One shard's fan-out worker: drains that shard's dispatch backlog
    and delivers each committed batch to its watchers off every store
    lock.

    Holds the store only through a weakref between iterations, so an
    abandoned store's dispatchers exit instead of leaking polling
    threads per Store (tests construct thousands).  Fault-schedule
    exceptions escaping a delivery are contained — a poisoned offer must
    not take the shard's fan-out path down (and the handoff path
    restarts the thread if something interpreter-grade does)."""
    while True:
        store = store_ref()
        if store is None:
            return
        shard = store._shards[sid]
        batch = None
        # deadline-bounded predicate loop: doze until a batch arrives,
        # re-checking the backlog under the SAME acquisition after every
        # wakeup (graftlint atomicity cv-discipline), but still fall out
        # after ~0.2 s so the strong store/shard refs drop and an
        # abandoned store can be collected
        doze = time.monotonic() + 0.2
        with shard._dispatch_cv:
            while not shard._dispatch_backlog:
                remaining = doze - time.monotonic()
                if remaining <= 0:
                    break
                shard._dispatch_cv.wait(remaining)
            if shard._dispatch_backlog:
                batch = shard._dispatch_backlog.popleft()
                # close() waits for backlog-empty AND not-inflight, so a
                # batch mid-fan-out still blocks a graceful shutdown
                shard._dispatch_inflight = True  # graftlint: disable=obligations -- armed only when a batch popped; the fan-out finally below clears it under the same cv (the batch-is-None correlation is beyond the engine)
                _ledger.push("dispatch_inflight", id(shard))
        if batch is not None:
            try:
                store._fan_out(*batch)
            except Exception:  # noqa: BLE001 — delivery containment
                logging.getLogger(__name__).exception(
                    "watch fan-out batch failed; continuing"
                )
            finally:
                with shard._dispatch_cv:
                    shard._dispatch_inflight = False
                    _ledger.pop("dispatch_inflight", id(shard))
                    shard._dispatch_cv.notify_all()
        # drop the strong references before sleeping so GC can collect
        # an otherwise-abandoned store
        store = None
        shard = None
        batch = None
