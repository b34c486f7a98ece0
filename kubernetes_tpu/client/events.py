"""EventRecorder: the record/events broadcaster reduced to store writes
with client-go-style aggregation.

Reference: client-go tools/record (EventBroadcaster/EventRecorder) and
the scheduler's call sites (fwk.EventRecorder().Eventf,
schedule_one.go:1003,1094).  Repeats of the same (object, reason,
message) bump `count` on one Event object instead of flooding the store
— the events correlator's aggregation behaviour.

Two modes:
  sync (default)  — eventf writes through immediately (tests, CLI).
  async           — eventf enqueues and a broadcaster thread drains on
                    a short interval, coalescing repeats in-queue
                    before they ever hit the store.  This is the
                    reference's actual shape (the broadcaster's
                    buffered channel; record.go NewBroadcaster): a bind
                    wave of 4k pods must not pay 4k synchronous store
                    writes on the scheduling thread.

Expiry (the apiserver's --event-ttl).  The recorder keeps its own expiry
order and never lists the kind on the write path: every write it makes
(a create, and a repeat that bumps `count` and `last_timestamp`) pushes
`(last_timestamp, namespace, name)` on a heap, and every 256th write
sweeps it: entries are popped while the oldest is past the TTL, and an
event is deleted only if the STORED event's `last_timestamp` is still
that old (a bumped event has a younger entry further down; one somebody
else deleted is skipped).  A heap and not a queue because writes do not
arrive in clock order to the last millisecond: a flush writes a
coalesced repeat under its latest timestamp in its first position, and
sync callers race between the clock read and the write.  So one write
costs one `get`, one `update` or `create` and one heap push whatever the
store holds, and a sweep costs what expired (one `get` and one `delete`
each), not what is stored.

Events this recorder did not write are entered by `resync`, off the
write path and by reference (`Store.list`'s selector sees the stored
objects before any copy is made): the events in the store when the
recorder is built (a Store recovered from a journal), and, where two
recorders share one store, the other's.  Replicated schedulers do: a
warm standby writes nothing, so the scheduler resyncs its recorder on
every acquisition of leadership (`Scheduler._reconcile_leadership`) and
the former leader's Events become the new leader's to expire.  A resync
that fails (a list error) is logged and made again by the next sweep.

In the flight recorder (utils/trace.py): span `events.flush` around each
non-empty flush (n = events written, a0 = events dropped at the queue
cap since the last flush) and a row `events.expire` a sweep (n = entries
examined, a0 = events deleted).
"""

from __future__ import annotations

import heapq
import logging
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from ..api import store as st
from ..api import types as api
from ..utils import trace

_QUEUE_CAP = 8192  # broadcaster channel capacity; overflow drops (record.go)


class EventRecorder:
    def __init__(
        self,
        store: st.Store,
        component: str = "default-scheduler",
        ttl: float = 3600.0,
        clock=time.time,
        async_mode: bool = False,
        flush_interval: float = 0.05,
    ):
        self.store = store
        self.component = component
        # the reference apiserver bounds Events with a TTL (default 1h,
        # --event-ttl); without expiry a long-running scheduler grows the
        # store (and journal compactions) without bound
        self.ttl = ttl
        self._clock = clock
        self._writes = 0
        # a heap of (last_timestamp, namespace, name): one per write,
        # and what resync found in the store
        self._expiry: List[Tuple[float, str, str]] = []
        self._expiry_lock = threading.Lock()
        self._resync_due = False  # the last resync failed: the next sweep retries
        self.resync()
        self._async = async_mode
        self._flush_interval = flush_interval
        self._queue: List[Tuple[Any, str, str, str, float]] = []
        self._dropped = 0  # eventf calls refused at the cap since the last flush
        self._qlock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        if async_mode:
            self._thread = threading.Thread(
                target=self._broadcaster, name="event-broadcaster", daemon=True
            )
            self._thread.start()

    def eventf(
        self, obj: Any, event_type: str, reason: str, message: str
    ) -> None:
        """Record one event for obj; never raises into the caller (events
        are best-effort observability, not control flow)."""
        if self._async:
            with self._qlock:
                if len(self._queue) < _QUEUE_CAP:
                    self._queue.append(
                        (obj, event_type, reason, message, self._clock())
                    )
                else:
                    self._dropped += 1
            return
        try:
            self._record(obj, event_type, reason, message, self._clock())
        except Exception:
            pass

    # -- async broadcaster --------------------------------------------------

    def _broadcaster(self) -> None:
        while not self._stop.wait(self._flush_interval):
            self.flush()
        self.flush()

    def flush(self) -> None:
        """Drain the queue, coalescing repeats of (object, reason,
        message) into one store write with the summed count."""
        with self._qlock:
            batch, self._queue = self._queue, []
            dropped, self._dropped = self._dropped, 0
        if not batch:
            return  # nothing was dropped either: a drop needs a full queue
        with trace.span("events.flush") as sp:
            sp.a0 = dropped
            sp.n = self._write_merged(batch)

    def _write_merged(self, batch: list) -> int:
        """One store write per distinct event of the batch; how many
        were written."""
        merged: Dict[Tuple[str, str, str, str], list] = {}
        for obj, event_type, reason, message, ts in batch:
            # event_type is part of the identity (matching _record's
            # same-type check): a Normal and a Warning repeat of the same
            # reason/message must not merge into one record whose type is
            # whichever arrived first
            key = (
                obj.meta.namespace,
                f"{obj.meta.name}.{reason.lower()}",
                event_type,
                message,
            )
            slot = merged.get(key)
            if slot is None:
                merged[key] = [obj, event_type, reason, message, ts, 1]
            else:
                slot[4] = ts
                slot[5] += 1
        written = 0
        for obj, event_type, reason, message, ts, n in merged.values():
            try:
                self._record(obj, event_type, reason, message, ts, count=n)
                written += 1
            except Exception:
                pass
        return written

    def stop(self) -> None:
        self._stop.set()
        if self._thread:
            self._thread.join(timeout=2)
        self.flush()

    # -- write-through ------------------------------------------------------

    def _record(
        self,
        obj: Any,
        event_type: str,
        reason: str,
        message: str,
        now: float,
        count: int = 1,
    ) -> None:
        meta = obj.meta
        name = f"{meta.name}.{reason.lower()}"
        self._writes += 1
        if self._writes % 256 == 0:
            self._expire(now)
        try:
            ev = self.store.get("Event", name, meta.namespace)
            if ev.message == message and ev.type == event_type:
                ev.count += count
                ev.last_timestamp = now
                self.store.update(ev, force=True, copy_result=False)
                self._note_written(now, meta.namespace, name)
                return
            self.store.delete("Event", name, meta.namespace)
        except KeyError:
            pass
        self.store.create(
            api.Event(
                meta=api.ObjectMeta(name=name, namespace=meta.namespace),
                involved_object=api.ObjectReference(
                    kind=getattr(obj, "KIND", ""),
                    name=meta.name,
                    namespace=meta.namespace,
                    uid=meta.uid,
                ),
                reason=reason,
                message=message,
                type=event_type,
                first_timestamp=now,
                last_timestamp=now,
                source_component=self.component,
                count=count,
            )
        )
        self._note_written(now, meta.namespace, name)

    # -- expiry -------------------------------------------------------------

    def _note_written(self, last_timestamp: float, namespace: str, name: str) -> None:
        with self._expiry_lock:
            heapq.heappush(self._expiry, (last_timestamp, namespace, name))

    def resync(self) -> None:
        """Enter every event the store holds that the expiry order lacks:
        the one pass over the kind when the recorder is built, and again
        when another recorder has been writing to the same store (a new
        leader calls it).  The selector reads the stored objects and
        keeps none, so nothing is copied.  Never raises: a list that
        fails leaves the order as it was, and the next sweep tries again."""
        found: List[Tuple[float, str, str]] = []

        def note(ev: api.Event) -> bool:
            found.append((ev.last_timestamp, ev.meta.namespace, ev.meta.name))
            return False

        try:
            self.store.list("Event", selector=note)
        except Exception:  # noqa: BLE001 — events are best-effort
            logging.getLogger(__name__).exception(
                "event recorder: resync failed; the next sweep retries"
            )
            self._resync_due = True
            return
        self._resync_due = False
        with self._expiry_lock:
            # a write made meanwhile is in `found`, in the heap or both
            self._expiry = list(set(self._expiry).union(found))
            heapq.heapify(self._expiry)

    def _expire(self, now: float) -> None:
        """Drop events past the TTL (the --event-ttl sweep): pop the
        entries that are, and delete an event whose stored
        `last_timestamp` still is."""
        if self._resync_due:
            self.resync()
        t0 = trace.now()
        due = []
        with self._expiry_lock:
            while self._expiry and now - self._expiry[0][0] > self.ttl:
                due.append(heapq.heappop(self._expiry))
        deleted = 0
        for _, namespace, name in due:
            try:
                ev = self.store.get("Event", name, namespace)
                if now - ev.last_timestamp > self.ttl:
                    self.store.delete("Event", name, namespace)
                    deleted += 1
            except KeyError:
                pass  # somebody else deleted it
        trace.event("events.expire", t0, trace.now(), n=len(due), a0=deleted)
