"""The program's flight recorder: spans, per-pod stamps, slow-cycle traces.

Reference: utiltrace.New("Scheduling", ...) with LogIfLong(100ms) steps
inside schedulePod (schedule_one.go:391-431) — the lightweight always-on
layer under the OTel integration.  Here the always-on layer is one
process-wide recorder (the chip belongs to one process; so does the
recorder): two fixed-size rings of plain doubles that every layer writes
to and any reader snapshots afterwards.

  spans   one row per timed interval: wall start/end AND the thread's
          own CPU time at both ends, thread, cycle, parent, a count and
          two numeric attributes.  Wall minus CPU is time the thread
          wanted to run and did not: the interpreter lock, another lock,
          the journal's I/O, the device.  Each span is also entered as a
          ``jax.profiler.TraceAnnotation`` of the same name, so in a
          profiler session it lies on the device trace's clock.
  tallies intervals too short and too many for a row each (a client's
          write, its journal append): ``tally()`` sums them into one
          row per thread, name and tenth of a second — how many, how
          long in all, wall time only, no annotation.
  pods    one row per pending pod: the six stamps of its path
          (enqueued, popped, solved, commit_begin, committed, failed),
          its cycle, attempts and route.  No CPU reading per pod.
  cpu     the CPU ledger: while a ``Scheduler`` runs, one sampler
          thread reads every Python thread's own CPU clock and the
          process's every quarter second and writes what advanced into
          the span ring (``proc.cpu``, ``proc.cpu.process``), with how
          late the wake ran past its timer: how long a thread that wants
          the interpreter waits for it (``proc.stall`` past a second).

One clock, ``time.perf_counter()``, read directly (never a scheduler's
injectable ``clock=``): a harness that times its client on the same
clock subtracts without conversion.  No lock is taken on any stamp:
slots come from ``itertools.count()`` and a stamp is a clock read and a
store into a preallocated buffer, so nothing here grows the heap the
collector walks.  A ring that laps counts what it overwrote
(``dropped_spans()``, ``dropped_pods()``) and ``snapshot()`` returns
None rather than rows from a torn interval.

``Trace`` is the utiltrace analogue over the same ring: a root span with
a fresh cycle id, ``step()`` children, one log line when the total
passes the threshold — now with what the OTHER threads were doing
meanwhile (their overlapping spans with wall and CPU seconds), any
collection inside it and, where the ledger runs, every thread's CPU.

The module imports only the standard library: the storage layer stamps
here too, and a client that imports the store loads neither numpy nor
JAX for it.  The rings are anonymous maps (pages are taken as rows are
written), the readers import numpy when called, and a span is annotated
only in a process that has loaded JAX, the only kind a profiler session
can run in.
"""

from __future__ import annotations

import gc
import heapq
import itertools
import logging
import mmap
import sys
import threading
import time
from collections import deque
from typing import Dict, List, Optional

logger = logging.getLogger("kubernetes_tpu.trace")

now = time.perf_counter
_cpu = time.thread_time
_ident = threading.get_ident
_NAN = float("nan")

SPAN_ROWS = 262_144
POD_ROWS = 131_072

# span row layout; ID is written last on open and zeroed first on reuse,
# so a reader never takes a half-written row for a whole one
_ID, _NAME, _START, _END, _CPU0, _CPU1, _TID, _CYCLE, _PARENT, _N, _A0, _A1 = range(12)
_SW = 12
SPAN_FIELDS = ("id", "name", "start", "end", "cpu0", "cpu1", "thread",
               "cycle", "parent", "n", "a0", "a1")

# pod row layout: the stage constants are what ``stamp`` takes
ENQUEUED, POPPED, SOLVED, COMMIT_BEGIN, COMMITTED, FAILED = range(1, 7)
CYCLE, ATTEMPTS, ROUTE, FAIL_CODE = range(7, 11)
_PW = 11
POD_FIELDS = ("key", "id", "enqueued", "popped", "solved", "commit_begin",
              "committed", "failed", "cycle", "attempts", "route",
              "fail_code")

ROUTES = ("greedy", "wavefront", "auction", "host")
ROUTE_ID = {r: i for i, r in enumerate(ROUTES)}
# FAIL_CODE values: how an attempt ended without a bind
FAIL_UNSCHEDULABLE, FAIL_ASSUME, FAIL_PERMIT, FAIL_BIND, \
    FAIL_MISSPECULATED, FAIL_SALVAGED, FAIL_UNENCODABLE = range(1, 8)

# a tallied row's ``parent``: it sums `n` short intervals that lie between
# its start and end and took ``a0`` seconds in all
TALLIED = -1
TALLY_S = 0.1

_names: List[str] = ["?"]
_name_ids: Dict[str, int] = {}
_tls = threading.local()
# the module's one lock: the once-only guard of every Trace's log line,
# taken only by a cycle already over its threshold, and the CPU ledger's
# start and stop.  No stamp takes it.
_LOCK = threading.Lock()
# which root spans passed their threshold; the durations stay in the ring
_overran: deque = deque(maxlen=256)
# (thread, name) -> [row id, row base, start of the row]: the open tallies
_tallies: Dict[tuple, list] = {}


def reset() -> None:
    """Fresh rings and slot numbers (tests)."""
    global _smv, _pmv, _pod_keys, _span_ctr, _pod_ctr
    _smv = memoryview(mmap.mmap(-1, SPAN_ROWS * _SW * 8)).cast("d")
    _pmv = memoryview(mmap.mmap(-1, POD_ROWS * _PW * 8)).cast("d")
    _pod_keys = [None] * POD_ROWS
    _span_ctr = itertools.count(1)
    _pod_ctr = itertools.count(1)
    _tallies.clear()
    _overran.clear()


reset()


def _name_id(name: str) -> int:
    i = _name_ids.get(name)
    if i is None:
        # two threads may intern one name at once: both ids then read
        # back as that name, which is all a reader asks
        _names.append(name)
        i = _name_ids[name] = _names.index(name)
    return i


# -- spans -----------------------------------------------------------------


class _NoAnnotation:
    """Stands in for the profiler's annotation in a process without JAX."""

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        pass


_NO_ANNOTATION = _NoAnnotation()
_annotation_cls = None      # jax.profiler.TraceAnnotation, once JAX is loaded


def _annotation(name: str):
    global _annotation_cls
    if _annotation_cls is None:
        if "jax" not in sys.modules:
            return _NO_ANNOTATION
        from jax.profiler import TraceAnnotation

        _annotation_cls = TraceAnnotation
    return _annotation_cls(name)


class _Span:
    """An open span: ``t0``/``t1`` are its wall reads (the timings a
    caller derives from a span come from these, not from clock reads of
    its own); ``n``, ``a0``, ``a1`` may be set until it closes."""

    __slots__ = ("id", "t0", "t1", "n", "a0", "a1",
                 "_base", "_prev", "_prev_cycle", "_ann")

    def __enter__(self) -> "_Span":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        t1 = now()
        c1 = _cpu()
        self._ann.__exit__(exc_type, exc, tb)
        mv, base = _smv, self._base
        if mv[base] == self.id:     # else a lap took the row meanwhile
            mv[base + _END] = t1
            mv[base + _CPU1] = c1
            mv[base + _N] = self.n
            mv[base + _A0] = self.a0
            mv[base + _A1] = self.a1
        self.t1 = t1
        _tls.cur = self._prev
        _tls.cycle = self._prev_cycle


def _open_row(name: str, start: float, cpu0: float, cycle: int,
              parent: int, n: float, i: Optional[int] = None) -> int:
    if i is None:
        i = next(_span_ctr)
    mv, base = _smv, (i % SPAN_ROWS) * _SW
    mv[base] = 0.0
    mv[base + _NAME] = _name_id(name)
    mv[base + _START] = start
    mv[base + _END] = _NAN
    mv[base + _CPU0] = cpu0
    mv[base + _CPU1] = _NAN
    mv[base + _TID] = _ident()
    mv[base + _CYCLE] = cycle
    mv[base + _PARENT] = parent
    mv[base + _N] = n
    mv[base + _A0] = 0.0
    mv[base + _A1] = 0.0
    mv[base] = i
    return i


def span(name: str, n: int = 0, cycle: Optional[int] = None,
         parent: Optional[int] = None) -> _Span:
    """Open a span; use as ``with span(...) as sp:``.  `cycle` and
    `parent` default to those of the span this thread has open (a
    worker that picks up another thread's work passes them).  A span
    costs a row, an annotation and two readings of the thread's CPU
    time (6 us each on the chip's host: PERF.md, PR 25): what happens
    once a pod or a write is a stamp or a ``tally``, not a span."""
    sp = _Span()
    tls = _tls
    sp._prev = prev = getattr(tls, "cur", 0)
    sp._prev_cycle = prev_cycle = getattr(tls, "cycle", 0)
    if parent is None:
        parent = prev
    if cycle is None:
        cycle = prev_cycle
    sp.n, sp.a0, sp.a1 = n, 0.0, 0.0
    sp._ann = ann = _annotation(name)
    ann.__enter__()
    c0 = _cpu()
    sp.t0 = t0 = now()
    sp.t1 = t0
    sp.id = i = _open_row(name, t0, c0, cycle, parent, n)
    sp._base = (i % SPAN_ROWS) * _SW
    tls.cur = i
    tls.cycle = cycle
    return sp


def event(name: str, t0: float, t1: float, n: float = 0,
          a0: float = 0.0, a1: float = 0.0) -> None:
    """A closed row for something whose interval the caller already
    holds (a compile that JAX timed, a count read back with a solve):
    a child of the span, and of the cycle, this thread has open (of
    none where none is open), with no CPU reading and no annotation:
    the cost is the row."""
    i = _open_row(name, t0, _NAN, getattr(_tls, "cycle", 0),
                  getattr(_tls, "cur", 0), n)
    base = (i % SPAN_ROWS) * _SW
    mv = _smv
    mv[base + _END] = t1
    mv[base + _A0] = a0
    mv[base + _A1] = a1


def tally(name: str, t0: float, t1: float) -> None:
    """Add the interval ``[t0, t1)`` to this thread's running row of
    `name`: one row per tenth of a second, not one per interval."""
    key = (_ident(), name)
    cur = _tallies.get(key)
    mv = _smv
    if cur is None or t1 - cur[2] > TALLY_S or mv[cur[1]] != cur[0]:
        i = _open_row(name, t0, _NAN, 0, TALLIED, 0)
        _tallies[key] = cur = [i, (i % SPAN_ROWS) * _SW, t0]
    base = cur[1]
    mv[base + _END] = t1
    mv[base + _N] += 1.0
    mv[base + _A0] += t1 - t0


# -- pods ------------------------------------------------------------------


def pod_slot(key) -> int:
    """A fresh pod row for `key`; the slot number goes where the pod's
    queue entry goes and is what ``stamp`` takes."""
    i = next(_pod_ctr)
    pos = i % POD_ROWS
    mv, base = _pmv, pos * _PW
    mv[base] = 0.0
    _pod_keys[pos] = key
    mv[base + ENQUEUED] = _NAN
    mv[base + POPPED] = _NAN
    mv[base + SOLVED] = _NAN
    mv[base + COMMIT_BEGIN] = _NAN
    mv[base + COMMITTED] = _NAN
    mv[base + FAILED] = _NAN
    mv[base + CYCLE] = 0.0
    mv[base + ATTEMPTS] = 0.0
    mv[base + ROUTE] = -1.0
    mv[base + FAIL_CODE] = 0.0
    mv[base] = i
    return i


def stamp(slot: int, stage: int, value: Optional[float] = None) -> None:
    """Store the clock (or `value`: one read shared by a group, or a
    cycle id, an attempt count, a route, a failure code) in a pod row.
    A slot the ring has lapped, and slot 0 (no row), are left alone."""
    base = (slot % POD_ROWS) * _PW
    if _pmv[base] == slot and slot:
        _pmv[base + stage] = now() if value is None else value


# -- the collector's pauses ------------------------------------------------

_gc_open = None     # (wall start, annotation) of the collection in progress


def _on_gc(phase: str, info: dict) -> None:
    global _gc_open
    if phase == "start":
        ann = _annotation("gc")
        ann.__enter__()
        _gc_open = (now(), ann)
    elif _gc_open is not None:
        t1 = now()
        t0, ann = _gc_open
        _gc_open = None
        ann.__exit__(None, None, None)
        # no CPU reading: a collection runs on the thread that tripped
        # it and waits for nothing, so its wall time is its CPU time
        i = _open_row("gc", t0, _NAN, getattr(_tls, "cycle", 0),
                      getattr(_tls, "cur", 0), info.get("collected", 0))
        base = (i % SPAN_ROWS) * _SW
        _smv[base + _END] = t1
        _smv[base + _A0] = info["generation"]


# one entry however often the module is loaded again
gc.callbacks[:] = [
    cb for cb in gc.callbacks
    if getattr(cb, "__module__", None) != __name__
]
gc.callbacks.append(_on_gc)


# -- the CPU ledger: who holds the interpreter -----------------------------

# a wake reads every Python thread's CPU clock and writes a row for each
# that ran
CPU_PERIOD_S = 0.25
# a wake later than this past its timer also writes a ``proc.stall`` row
STALL_S = 1.0
# the thread key of the process's CPU that no Python thread read holds:
# XLA's and the runtime's own threads, and threads that have ended
NATIVE = 0
LEDGER_ROWS = ("proc.cpu", "proc.cpu.process", "proc.stall")

# thread key -> name of every thread the ledger reads, and of every ended
# one a row in the ring still names, so that a reader after the threads
# are gone names them; a key is never given twice, so a thread that reuses
# an ended one's ident is another key
_thread_names: Dict[int, str] = {NATIVE: "native"}
_thread_keys = itertools.count(NATIVE + 1)
# (id of its last row, key) of each ended thread still named, least first:
# its name goes once the ring has overwritten that row (``_forget``)
_ended: List[tuple] = []
_ledger_owners: set = set()
_ledger = None      # the running _CpuLedger, or None


def thread_names() -> Dict[int, str]:
    """{thread key: name} of every thread the CPU ledger reads, and of every
    ended one that a ``proc.cpu`` or ``proc.stall`` row in the ring names
    (its ``n``)."""
    return dict(_thread_names)


def _retire(key: int, row: int) -> None:
    """Thread `key` has ended or is no longer read; `row` is the id of its
    last row, 0 if it wrote none (then nothing names it)."""
    if row:
        heapq.heappush(_ended, (row, key))
    else:
        _thread_names.pop(key, None)


def _forget() -> None:
    """Drop the names of ended threads whose last row the ring no longer
    holds: rows are overwritten in the order of their ids."""
    while _ended and _smv[(_ended[0][0] % SPAN_ROWS) * _SW + _ID] != _ended[0][0]:
        _thread_names.pop(heapq.heappop(_ended)[1], None)


def _ledger_row(name: str, t0: float, t1: float, tid: int, n: float,
                cpu0: float = _NAN, cpu1: float = _NAN,
                a0: float = 0.0, a1: float = 0.0) -> int:
    """A closed row of the ledger's, in no cycle and under no span, on
    behalf of thread `tid` (the row's ``thread``); returns its id."""
    i = _open_row(name, t0, cpu0, 0, 0, n)
    mv, base = _smv, (i % SPAN_ROWS) * _SW
    mv[base + _END] = t1
    mv[base + _CPU1] = cpu1
    mv[base + _TID] = tid
    mv[base + _A0] = a0
    mv[base + _A1] = a1
    return i


class _CpuLedger:
    """The sampler thread.  Every wake, on one clock read ``t`` (and the
    timer ``due`` it was woken for), it writes:

      proc.cpu          one row a Python thread whose CPU clock advanced
                        since its last row: zero length at ``t``,
                        ``cpu0`` / ``cpu1`` the thread's CPU seconds at
                        its last row and now, ``thread`` its ident, ``n``
                        its key (``thread_names()``).  An idle thread
                        writes none.
      proc.cpu.process  one row a wake: from ``due`` to ``t``, so its
                        length is how late the wake ran past its timer;
                        ``cpu0`` / ``cpu1`` the process's CPU seconds at
                        the last wake and now (read after every thread's,
                        so it holds their sum), ``n`` the threads read,
                        ``a0`` the wake's own seconds from ``t`` to this
                        row: what the ledger costs, on a clock finer
                        than a CPU clock that ticks.
      proc.stall        a wake more than STALL_S late: from ``due`` to
                        ``t``, ``n`` the key of the thread whose CPU
                        advanced most across it (NATIVE if no Python
                        thread's did as much as the rest of the
                        process), ``thread`` its ident, ``a0`` that
                        advance, ``a1`` the process's.
    """

    def __init__(self):
        self._stop = threading.Event()
        # Thread -> [key, CPU clock id or None, CPU seconds at its last row,
        #            id of its last row or 0]
        self._seen: dict = {}
        self._proc = time.process_time()
        self.thread = threading.Thread(target=self._run, name="trace-cpu",
                                       daemon=True)
        self.thread.start()

    def stop(self) -> None:
        self._stop.set()
        self.thread.join()
        for key, _, _, row in self._seen.values():
            _retire(key, row)       # a later ledger reads them under new keys
        self._seen = {}

    def _run(self) -> None:
        due = now() + CPU_PERIOD_S
        while not self._stop.wait(max(0.0, due - now())):
            self._read(due, now())
            due += CPU_PERIOD_S
            if due <= now():
                due = now() + CPU_PERIOD_S      # past a late wake: a period on

    def _read(self, due: float, t: float) -> None:
        seen, live = self._seen, {}
        for th in threading.enumerate():
            ent = seen.pop(th, None)
            if ent is None:
                if th.ident is None:
                    continue        # started, not running yet: the next wake
                key = next(_thread_keys)
                _thread_names[key] = th.name
                try:
                    clock = time.pthread_getcpuclockid(th.ident)
                except (OSError, AttributeError):
                    clock = None
                ent = [key, clock, 0.0, 0]
            live[th] = ent
        for key, _, _, row in seen.values():       # ended since the last wake
            _retire(key, row)
        self._seen = live
        _forget()
        top, top_th, ran = 0.0, None, 0.0
        for th, ent in live.items():
            if ent[1] is None:
                continue
            try:
                c = time.clock_gettime(ent[1])
            except OSError:
                continue            # it ended since enumerate()
            d = c - ent[2]
            if d > 0.0:
                ent[3] = _ledger_row("proc.cpu", t, t, th.ident, ent[0], ent[2], c)
                ent[2] = c
                ran += d
                if d > top:
                    top, top_th = d, th
        proc = time.process_time()
        _ledger_row("proc.cpu.process", due, t, _ident(), len(live),
                    self._proc, proc, a0=now() - t)
        if t - due > STALL_S:
            rest = proc - self._proc - ran
            if top_th is None or rest > top:
                _ledger_row("proc.stall", due, t, 0, NATIVE,
                            a0=rest, a1=proc - self._proc)
            else:
                ent = live[top_th]
                ent[3] = _ledger_row("proc.stall", due, t, top_th.ident, ent[0],
                                     a0=top, a1=proc - self._proc)
        self._proc = proc


def start_cpu_ledger(owner) -> None:
    """Keep the CPU ledger running while `owner` runs (``Scheduler.start``):
    one sampler thread in the process however many owners; starting an
    owner that runs already changes nothing."""
    global _ledger
    with _LOCK:
        _ledger_owners.add(owner)
        if _ledger is None:
            _ledger = _CpuLedger()


def stop_cpu_ledger(owner) -> None:
    """`owner` has stopped (``Scheduler.stop``); once no owner is left the
    sampler thread has ended when this returns.  Stopping twice is once."""
    global _ledger
    with _LOCK:
        _ledger_owners.discard(owner)
        if _ledger is not None and not _ledger_owners:
            _ledger.stop()
            _ledger = None


# -- reading ---------------------------------------------------------------


def _table(mv, width: int, rows: int, ctr) -> tuple:
    """(the part of the ring written so far as a 2-D view, the next slot
    number).  Takes that slot number as the high-water mark; its row is
    never written and drops out with the lap it belongs to."""
    import numpy as np

    hi = next(ctr)
    return np.frombuffer(mv).reshape(rows, width)[:min(hi, rows)], hi


def _valid_rows(mv, width: int, rows: int, ctr) -> tuple:
    """(the ring's whole rows sorted by id, rows lost to laps so far)."""
    import numpy as np

    table, hi = _table(mv, width, rows, ctr)
    ids = table[:, 0]
    keep = table[(ids > 0) & (ids < hi) & (ids > hi - rows)]
    return keep[np.argsort(keep[:, 0], kind="stable")], max(0, hi - rows)


def dropped_spans() -> int:
    """Span rows overwritten by laps of the ring so far."""
    return max(0, next(_span_ctr) - SPAN_ROWS)


def dropped_pods() -> int:
    return max(0, next(_pod_ctr) - POD_ROWS)


def _clean(row: list) -> list:
    return [None if v != v else v for v in row]


def snapshot(t0: float = float("-inf"), t1: float = float("inf")) -> Optional[dict]:
    """The rows that START inside ``[t0, t1)`` as plain lists
    (``SPAN_FIELDS`` / ``POD_FIELDS`` order; a pod row starts at its
    ``enqueued``) and the rows lost to laps so far.  An unset time
    reads None.  None where a ring has lapped into the interval: no
    number comes from torn rows."""
    import numpy as np

    out = {"routes": ROUTES}
    for what, buf, width, rows, ctr, col in (
        ("spans", _smv, _SW, SPAN_ROWS, _span_ctr, _START),
        ("pods", _pmv, _PW, POD_ROWS, _pod_ctr, ENQUEUED),
    ):
        keep, dropped = _valid_rows(buf, width, rows, ctr)
        out["dropped_" + what] = dropped
        if dropped and len(keep):
            # what was overwritten started no later than the oldest
            # survivors (slots are taken in starting order, give or
            # take a thread switch: hence a few rows, not one)
            oldest = keep[:16, col]
            if not t0 > np.nanmax(oldest):
                return None
        starts = keep[:, col]
        keep = keep[(starts >= t0) & (starts < t1)]
        if what == "spans":
            out[what] = [
                [int(r[0]), _names[int(r[1])]] + _clean(r[2:6])
                + [int(r[6]), int(r[7]), int(r[8]), int(r[9]), r[10], r[11]]
                for r in keep.tolist()
            ]
        else:
            out[what] = [
                [_pod_keys[int(r[0]) % POD_ROWS], int(r[0])] + _clean(r[1:7])
                + [int(r[7]), int(r[8]), int(r[9]), int(r[10])]
                for r in keep.tolist()
            ]
    return out


def _cpu_by_thread(t, t0: float, t1: float) -> str:
    """From the ledger's rows in table `t`: the CPU seconds each thread
    spent from the last wake at or before `t0` to the last wake so far
    (by name, the most first; ``native`` the rest of the process), and
    every stall that overlapped ``[t0, t1)`` with the thread that ran
    through it.  Empty where the ledger has no wake before `t0`."""
    import numpy as np

    live = t[t[:, _ID] > 0]
    wakes = live[live[:, _NAME] == _name_ids.get("proc.cpu.process", -1)]
    before = wakes[wakes[:, _END] <= t0]
    if not len(before):
        return ""
    w0 = before[np.argmax(before[:, _END])]
    w1 = wakes[np.argmax(wakes[:, _END])]
    if w1[_END] <= w0[_END]:
        return ""
    rows = live[(live[:, _NAME] == _name_ids.get("proc.cpu", -1))
                & (live[:, _START] > w0[_END]) & (live[:, _START] <= w1[_END])]
    by: dict = {}
    for r in rows.tolist():
        who = _thread_names.get(int(r[_N]), "?")
        by[who] = by.get(who, 0.0) + r[_CPU1] - r[_CPU0]
    rest = (w1[_CPU1] - w0[_CPU1]) - sum(by.values())
    if rest > 0.0:
        by[_thread_names[NATIVE]] = rest
    out = f"; cpu over {w1[_END] - w0[_END]:.3f}s: " + ", ".join(
        f"{who} {cpu:.3f}s" for who, cpu in sorted(by.items(), key=lambda kv: -kv[1])
    )
    stalls = live[(live[:, _NAME] == _name_ids.get("proc.stall", -1))
                  & (live[:, _START] < t1) & (live[:, _END] > t0)]
    for r in stalls.tolist():
        out += (f"; stall {r[_END] - r[_START]:.3f}s: "
                f"{_thread_names.get(int(r[_N]), '?')} cpu {r[_A0]:.3f}s")
    return out


# -- Trace: a cycle's root span, logged when slow --------------------------


def drain_overruns() -> List[Dict]:
    """Return and clear the over-threshold traces.  Each entry:
    {name, total_s, threshold_s, fields, steps: [(what, seconds)]};
    the steps are read from the ring, and are empty once it has lapped
    over them."""
    out = []
    while _overran:
        tr, total, limit = _overran.popleft()
        out.append({
            "name": tr.name,
            "total_s": round(total, 4),
            "threshold_s": limit,
            "fields": dict(tr.fields),
            "steps": [(w, round(dt, 4)) for w, dt in tr.steps],
        })
    return out


class Trace:
    """One cycle: a root span with a fresh cycle id (``id``), children
    opened with ``span()`` on the same thread or closed with ``step()``,
    and one log line if the whole passes the threshold.

    `start` is where the root row begins if that was before now (the
    clock read that ended the scheduler's pop, which is its pods'
    ``popped``); ``total``, the threshold and the root's CPU reading
    count from now all the same: in between, the lane finishes the
    cycle before this one, which is not this cycle's time.  `clock` is for tests; the
    scheduler never passes one."""

    def __init__(self, name: str, threshold: float = 0.1,
                 clock=time.perf_counter, span: Optional[str] = None,
                 start: Optional[float] = None, **fields):
        self.name = name
        self.threshold = threshold
        self._clock = clock
        self.fields = fields
        self._end: Optional[float] = None
        self._logged = False
        self._cpu_last = _cpu()
        self._t0 = self._last = clock()
        self.start = self._t0 if start is None else start
        self.id = next(_span_ctr)
        _open_row(span or name, self.start, self._cpu_last, self.id, 0,
                  fields.get("pods", 0), i=self.id)
        _tls.cur = _tls.cycle = self.id

    def step(self, what: str) -> None:
        """Close a child span that began where the last one ended."""
        t, c = self._clock(), _cpu()
        i = _open_row(what, self._last, self._cpu_last, self.id, self.id, 0)
        base = (i % SPAN_ROWS) * _SW
        _smv[base + _END] = t
        _smv[base + _CPU1] = c
        self._last, self._cpu_last = t, c

    @property
    def total(self) -> float:
        end = self._clock() if self._end is None else self._end
        return end - self._t0

    def close(self, a0: float = 0.0, a1: float = 0.0) -> None:
        """End the root span (once) and leave the thread with no open
        cycle."""
        if self._end is not None:
            return
        self._end = self._clock()
        mv, base = _smv, (self.id % SPAN_ROWS) * _SW
        if mv[base] == self.id:
            mv[base + _END] = self._end
            mv[base + _CPU1] = _cpu()
            mv[base + _A0] = a0
            mv[base + _A1] = a1
        if getattr(_tls, "cycle", 0) == self.id:
            _tls.cur = _tls.cycle = 0

    def __enter__(self) -> "Trace":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()
        self.log_if_long()

    # -- what the ring holds of this cycle ---------------------------------

    @property
    def steps(self) -> list:
        """(name, seconds) of the root's direct children on its own
        thread, in starting order (a zero-length row is a count that
        rode on the cycle, not a step of it)."""
        import numpy as np

        t = _table(_smv, _SW, SPAN_ROWS, _span_ctr)[0]
        root = t[self.id % SPAN_ROWS]
        if root[_ID] != self.id:
            return []
        kids = t[(t[:, _PARENT] == self.id) & (t[:, _ID] > self.id)
                 & (t[:, _TID] == root[_TID]) & (t[:, _END] > t[:, _START])]
        kids = kids[np.argsort(kids[:, _START], kind="stable")]
        return [(_names[int(k[_NAME])], float(k[_END] - k[_START])) for k in kids]

    def _compiles(self) -> str:
        """The executables this cycle's thread built or loaded under it
        (``sched.compile`` rows, utils/compileclock.py): they lie inside
        a step, so the steps alone would not name them."""
        t = _table(_smv, _SW, SPAN_ROWS, _span_ctr)[0]
        rows = t[(t[:, _CYCLE] == self.id) & (t[:, _ID] > self.id)
                 & (t[:, _NAME] == _name_ids.get("sched.compile", -1))]
        if not len(rows):
            return ""
        return f"; of which sched.compile x{len(rows)}: {rows[:, _A0].sum() * 1e3:.1f}ms"

    def _meanwhile(self) -> str:
        """What ran beside this cycle: the other threads' spans that
        overlapped it (count and wall seconds by thread and name, and
        the thread's CPU seconds where every one of them has the
        reading: a tally, a collection and a span still open have none),
        every collection inside it, and every thread's CPU from the
        ledger (``_cpu_by_thread``)."""
        import numpy as np

        t = _table(_smv, _SW, SPAN_ROWS, _span_ctr)[0]
        root = t[self.id % SPAN_ROWS]
        if root[_ID] != self.id:
            return ""
        t0 = root[_START]
        t1 = self._clock() if self._end is None else self._end
        ends = np.where(t[:, _END] == t[:, _END], t[:, _END], t1)
        hit = t[(t[:, _ID] > 0) & (t[:, _START] < t1) & (ends > t0)]
        gc_id = _name_ids.get("gc", -1)
        ledger = {_name_ids.get(n, -1) for n in LEDGER_ROWS}
        threads = {th.ident: th.name for th in threading.enumerate()}
        agg: dict = {}
        for r in hit.tolist():
            if r[_NAME] in ledger:
                continue
            if r[_NAME] == gc_id:
                key = ("gc", f"gen{int(r[_A0])}")
            elif r[_TID] != root[_TID]:
                tid = int(r[_TID])
                key = (threads.get(tid, str(tid)), _names[int(r[_NAME])])
            else:
                continue
            a = agg.setdefault(key, [0, 0.0, 0.0])
            if r[_PARENT] == TALLIED:
                a[0] += int(r[_N])
                a[1] += r[_A0]
                a[2] = _NAN
            else:
                a[0] += 1
                a[1] += (t1 if r[_END] != r[_END] else r[_END]) - r[_START]
                a[2] += r[_CPU1] - r[_CPU0]
        by_thread: dict = {}
        for (who, what), (k, wall, cpu) in sorted(agg.items()):
            by_thread.setdefault(who, []).append(
                f"{what} x{k} wall {wall:.3f}s"
                + ("" if cpu != cpu else f" cpu {cpu:.3f}s")
            )
        gcs = by_thread.pop("gc", None)
        out = ""
        if by_thread:
            out += "; meanwhile " + " | ".join(
                f"{who}: " + ", ".join(v) for who, v in by_thread.items()
            )
        if gcs:
            out += "; gc " + ", ".join(gcs)
        return out + _cpu_by_thread(t, t0, t1)

    def log_if_long(self, threshold: Optional[float] = None) -> None:
        limit = self.threshold if threshold is None else threshold
        total = self.total
        # Exactly once per trace, even when a caller's explicit exit-path
        # call races or stacks with the with-block exit (the r05 bench
        # tail showed every over-threshold schedule_batch trace twice —
        # the explicit call at the end of the group loop plus __exit__,
        # each formatting its own slightly-later total).  The flag is
        # checked-and-set under a lock so a trace finalized from another
        # thread (deferred-cycle finalize) can't double-emit either.
        if total < limit:
            return
        with _LOCK:
            if self._logged:
                return
            self._logged = True
        tags = ",".join(f"{k}={v}" for k, v in self.fields.items())
        parts = "; ".join(f"{w}: {dt * 1e3:.1f}ms" for w, dt in self.steps)
        logger.warning(
            "trace %s (%s) took %.1fms (threshold %.0fms): %s%s%s",
            self.name, tags, total * 1e3, limit * 1e3, parts,
            self._compiles(),
            self._meanwhile() if self._clock is time.perf_counter else "",
        )
        _overran.append((self, total, limit))
