"""Deterministic, seeded fault injection for the solve→assume→bind
pipeline.

The registry is the chaos suite's only lever: named fault points are
threaded through the hot path (journal append/fsync, the wave
transaction, the checkpoint writer, watch fan-out and the consumer side
of watch streams, the list/relist path, the device solve, the binder
commit, lease renewal)
and each point consults the armed registry through one module-level
indirection.  Disarmed — the production state — the check
is a single global load and an early return.

Schedules are bounded and seeded: a `FaultRegistry(seed=N)` draws every
probabilistic decision from its own `random.Random(N)`, so a failing
chaos seed replays byte-identically.  Supported schedule kinds:

  fail(point, n)        raise (fail-once / fail-N); custom exception type
  crash(point, n)       raise FaultCrash — a BaseException that escapes
                        `except Exception` containment and kills the
                        worker thread (binder-supervision coverage)
  delay(point, s, n)    sleep `s` seconds (latency injection)
  torn_write(point)     the caller writes a PREFIX of its payload and
                        then fails (journal torn-tail coverage)
  drop(point, n)        the caller discards its payload (watch.offer →
                        simulated slow watcher)
  corrupt(point, n)     the caller poisons its result (batch.solve →
                        NaN score tensor)

Sites that need caller-interpreted behaviour (torn/drop/corrupt) read
fire()'s return value; exception-kind schedules raise from inside
fire() so most sites need no control flow at all.
"""

from __future__ import annotations

import contextlib
import threading
import time
from dataclasses import dataclass, field
from random import Random
from typing import Dict, List, Optional

from ..analysis import ledger as _ledger

# Every fault point the hot path exposes.  fail()/crash()/... validate
# against this set so a typo'd point name fails the test loudly instead
# of silently never firing.
KNOWN_POINTS = frozenset({
    "store.journal.append",
    "store.journal.fsync",
    "store.update_wave",
    # per-shard twins of the journal/wave points: fired with the shard
    # index in ctx so a schedule lands on the FIRST shard that reaches
    # the point — the crash-one-shard chaos family (surviving shards
    # must stay consistent while the crashed one recovers)
    "store.shard.journal.append",
    "store.shard.update_wave",
    "store.checkpoint",
    "store.list",
    "watch.offer",
    "watch.consume",
    "batch.solve",
    # the batched PostFilter dry-run (one [P, N, K] dispatch per pass);
    # corrupt-grade schedules poison the decoded result so the health
    # check trips and the pass falls back to the per-pod parity path
    "batch.preemption",
    "binder.commit_wave",
    # a batch dispatched SPECULATIVELY — encode/solve over an earlier
    # wave's assumed placements while that wave is still committing;
    # fail-grade schedules kill the dispatch (the cycle containment
    # requeues exactly the speculative batch)
    "solve.speculate",
    # a streamed per-store-shard sub-wave handed to the commit pool as
    # its slice of the wave finished staging (before the rest staged)
    "binder.stream_subwave",
    # a gang carve-out batch dispatched to the device (slice family
    # armed, gangs present) — fail-grade schedules kill the solve and
    # ride the batch.solve retry/breaker containment; the carve-out
    # chaos family (seeds 600-604) asserts no partially occupied
    # carve-out survives quiesce
    "solve.carveout",
    # the incremental-solve partials sync (models/partials.py): CORRUPT
    # poisons the resident partials with NaN score rows so the decode
    # health check trips and the retry path falls back to a full
    # recompute / breaker fallback (the parity gate's runtime wire);
    # fail-grade schedules make the batch solve cold instead — the
    # partials chaos family (seeds 700-704)
    "solve.partials",
    # the elastic node axis's in-place resident resize (models/mirror.py
    # _resize_resident, a pad-bucket crossing absorbed without a full
    # re-upload): fail-grade schedules decline the resize — the mirror
    # takes the full (RESHARDED) re-upload safety path; CORRUPT poisons
    # the carried rows so the decode health check trips and the retry's
    # invalidation heals via full resync — the node-churn chaos family
    # (seeds 800-804)
    "mirror.grow",
    "leader.renew",
    # -- serving-plane points (api/server.py, api/flowcontrol.py) -------
    # every authorized HTTP request, fired before dispatch: fail-grade
    # schedules surface as 4xx/5xx to the client (retry containment),
    # delay-grade as server-side latency — the serving chaos family
    # (seeds 900-909)
    "server.request",
    # one chunked frame written to a watch stream: delay-grade models a
    # stalled TCP consumer (full socket buffer), fail-grade a mid-frame
    # client disconnect, torn-grade a partial frame write then error —
    # the per-watcher write deadline must expire the watch, never pin
    # the handler thread
    "server.watch.write",
    # APF admission (flowcontrol.APFGate.acquire): delay-grade stalls
    # admission (queue-wait coverage), fail-grade rejects the request
    # at the gate (surfaced as a 4xx by the handler's containment)
    "apf.admit",
    # one framed journal wave line (store._append_journal_wave after
    # framing.encode_frame): CORRUPT poisons the encoded frame bytes so
    # replay must drop it as a torn wave — exercised against BOTH the
    # native _hostplane CRC path and the pure-Python fallback (parity)
    "journal.frame",
})

# caller-interpreted actions returned by fire()
DROP = "drop"
CORRUPT = "corrupt"


class FaultInjected(RuntimeError):
    """The default injected failure."""


class FaultCrash(BaseException):
    """Escapes `except Exception` containment: the injected analogue of
    a worker thread dying outright (stack overflow, interpreter-level
    fault) — what binder supervision exists to recover from."""


@dataclass
class TornWrite:
    """Returned by fire(): write only `frac` of the payload, then fail."""

    frac: float = 0.5


@dataclass
class _Schedule:
    mode: str                 # fail | crash | delay | torn | drop | corrupt
    remaining: int            # fires left; -1 = unbounded
    exc: type = FaultInjected
    seconds: float = 0.0
    probability: float = 1.0
    frac: float = 0.5


class FaultRegistry:
    """One chaos run's fault plan: schedules per point, consumed in
    registration order, every probabilistic draw from the run's seed."""

    GUARDED_FIELDS = {
        "_schedules": "_lock",
        "_rng": "_lock",
        "fired": "_lock",
        "log": "_lock",
        "last_ctx": "_lock",
    }
    # schedule registration precedes arm(): the builder-style fail()/
    # crash()/... calls run single-threaded before any hot-path thread
    # can reach fire()
    LOCKED_METHODS = frozenset({"_add"})

    def __init__(self, seed: int = 0):
        self.seed = seed
        self._rng = Random(seed)
        self._lock = threading.Lock()
        self._schedules: Dict[str, List[_Schedule]] = {}
        # observability for the suite's coverage assertions
        self.fired: Dict[str, int] = {}
        self.log: List[tuple] = []  # (point, mode)
        # fire-site context of the LAST schedule that fired per point
        # (e.g. {"shard": 2} from the store's per-shard points) — the
        # crash-one-shard chaos family reads which shard it killed
        self.last_ctx: Dict[str, dict] = {}

    # -- schedule registration -------------------------------------------

    def _add(self, point: str, sched: _Schedule) -> "FaultRegistry":
        if point not in KNOWN_POINTS:
            raise ValueError(
                f"unknown fault point {point!r}; known: {sorted(KNOWN_POINTS)}"
            )
        self._schedules.setdefault(point, []).append(sched)
        return self

    def fail(
        self,
        point: str,
        n: int = 1,
        exc: type = FaultInjected,
        probability: float = 1.0,
    ) -> "FaultRegistry":
        return self._add(
            point, _Schedule("fail", n, exc=exc, probability=probability)
        )

    def crash(
        self, point: str, n: int = 1, probability: float = 1.0
    ) -> "FaultRegistry":
        return self._add(
            point, _Schedule("crash", n, probability=probability)
        )

    def delay(
        self, point: str, seconds: float, n: int = 1, probability: float = 1.0
    ) -> "FaultRegistry":
        return self._add(
            point,
            _Schedule("delay", n, seconds=seconds, probability=probability),
        )

    def torn_write(
        self, point: str, frac: float = 0.5, n: int = 1
    ) -> "FaultRegistry":
        return self._add(point, _Schedule("torn", n, frac=frac))

    def drop(
        self, point: str, n: int = 1, probability: float = 1.0
    ) -> "FaultRegistry":
        return self._add(point, _Schedule("drop", n, probability=probability))

    def corrupt(
        self, point: str, n: int = 1, probability: float = 1.0
    ) -> "FaultRegistry":
        return self._add(
            point, _Schedule("corrupt", n, probability=probability)
        )

    def pending(self) -> Dict[str, int]:
        """Point → fires still scheduled (0 once a bounded plan drained;
        the chaos suite's bounded-quiesce precondition)."""
        with self._lock:
            return {
                point: sum(
                    s.remaining for s in scheds if s.remaining > 0
                )
                for point, scheds in self._schedules.items()
            }

    # -- the hot-path side ------------------------------------------------

    def fire(self, point: str, **ctx):
        delay_s = 0.0
        action = None
        exc: Optional[BaseException] = None
        with self._lock:
            for sched in self._schedules.get(point, ()):
                if sched.remaining == 0:
                    continue
                if (
                    sched.probability < 1.0
                    and self._rng.random() >= sched.probability
                ):
                    continue
                if sched.remaining > 0:
                    sched.remaining -= 1
                self.fired[point] = self.fired.get(point, 0) + 1
                self.log.append((point, sched.mode))
                self.last_ctx[point] = dict(ctx)
                if sched.mode == "delay":
                    delay_s = sched.seconds
                    continue  # latency composes with a later failure
                if sched.mode == "fail":
                    exc = sched.exc(f"injected fault at {point}")
                elif sched.mode == "crash":
                    exc = FaultCrash(f"injected crash at {point}")
                elif sched.mode == "torn":
                    action = TornWrite(sched.frac)
                elif sched.mode == "drop":
                    action = DROP
                elif sched.mode == "corrupt":
                    action = CORRUPT
                break  # at most one non-delay schedule fires per call
        if delay_s > 0.0:
            time.sleep(delay_s)
        if exc is not None:
            raise exc
        return action


# -- module-level arming ----------------------------------------------------

_registry: Optional[FaultRegistry] = None


def arm(registry: FaultRegistry) -> FaultRegistry:
    global _registry
    if _registry is not None:
        # re-arm over a live registry: the previous arming's obligation
        # is retired by being overwritten, not leaked
        _ledger.discharge("fault", 0)
    _registry = registry
    _ledger.acquire("fault", 0)
    return registry


def disarm() -> None:
    global _registry
    if _registry is not None:
        _ledger.discharge("fault", 0)
    _registry = None


@contextlib.contextmanager
def armed(registry: FaultRegistry):
    arm(registry)
    try:
        yield registry
    finally:
        disarm()


def fire(point: str, **ctx):
    """The hot-path entry: a single global load when disarmed."""
    reg = _registry
    if reg is None:
        return None
    return reg.fire(point, **ctx)


# -- crash-restart harness ---------------------------------------------------
#
# The kill-restart chaos suite simulates process death WITHOUT fd
# hackery on the live store: a SIGKILL's disk image is exactly "the
# filesystem's bytes right now, minus whatever still sits in userspace
# buffers" — and copying the journal/snapshot files through the
# filesystem reproduces that by construction (a copy reads what the OS
# has, never what the dying process buffered).  The restarted store
# opens the image; the original store object is torn down ungracefully
# (Scheduler.kill(), no Store.close()) and abandoned.


def crash_disk_image(journal_path: str, dest_dir: str) -> str:
    """Capture the post-SIGKILL on-disk state of a journaled store:
    copy the journal(s) and checkpoint snapshot(s) (if present) into
    `dest_dir` as they exist on the filesystem RIGHT NOW — the 1-shard
    layout (``<path>`` + ``<path>.snap``) and the sharded layout
    (``<path>.s<i>`` + ``<path>.s<i>.snap``) both.  Returns the copied
    journal base path — hand it to ``Store(journal_path=...)`` to
    'restart' the killed store (the shard count is inferred from the
    copied layout).  Call while the victim is still live (or already
    abandoned); the copy never touches its file handles."""
    import glob
    import os
    import shutil

    os.makedirs(dest_dir, exist_ok=True)
    dest = os.path.join(dest_dir, os.path.basename(journal_path))
    copied = False
    for src in [journal_path, journal_path + ".snap"] + sorted(
        glob.glob(glob.escape(journal_path) + ".s*")
    ):
        if os.path.exists(src):
            suffix = src[len(journal_path):]
            shutil.copyfile(src, dest + suffix)
            copied = copied or not suffix.endswith(".snap")
    if not copied:
        open(dest, "w").close()
    return dest


def remove_snapshots(journal_path: str) -> int:
    """Delete every checkpoint snapshot of a store's on-disk layout
    (1-shard and sharded alike) — the full-journal-replay ORACLE mode
    the chaos suite compares snapshot+suffix recovery against.  Returns
    the number of snapshots removed."""
    import glob
    import os

    n = 0
    for p in [journal_path + ".snap"] + glob.glob(
        glob.escape(journal_path) + ".s*.snap"
    ):
        if os.path.exists(p):
            os.remove(p)
            n += 1
    return n
