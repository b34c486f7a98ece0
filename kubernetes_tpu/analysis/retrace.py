"""Runtime retrace tracker — the dynamic half of recompile-discipline.

The static pass (analysis/shapes.py) proves the bucket lattice is
closed under ``jax.eval_shape``; this tracker observes the XLA traces
that ACTUALLY happen while code runs and answers two questions the
static pass cannot:

  * did any executable key get traced TWICE (a genuine retrace — cache
    eviction, a config flip, or a non-hashable static leaking into the
    jit key)?  Always a failure.
  * did any trace happen during the STEADY window (after the harness
    called :func:`mark_steady`)?  A steady-state trace means a kernel
    argument escaped the pad-bucket lattice and ate a 10-40 s XLA
    compile on the hot path — the exact failure mode the pad buckets
    (utils.vocab.pad_dim) exist to prevent.
    tests/test_metrics_sources.py holds the served loop to it.

The solver jit wrappers (ops/assign.py ``greedy_assign_jit`` /
``wavefront_assign_jit``, ops/auction.py ``auction_assign_jit``) call
:func:`note` after every dispatch.  Disarmed cost is one module-global
None check; armed cost is one ``_cache_size()`` C-call plus — only on a
cache-size increase — one signature hash.

Usage (scoped, mirroring analysis/runtime.py's lock tracker)::

    from kubernetes_tpu.analysis import retrace

    with retrace.tracked() as tracker:
        ...                       # warmup: traces are expected
        retrace.mark_steady()
        ...                       # steady: any trace is a finding
    tracker.assert_no_steady_recompiles()

Under pytest, set ``GRAFTLINT_SHAPES=1`` to arm the tracker for the
whole session (tests/conftest.py wires the fixture); the session fails
if any executable key was traced twice.

This module is import-light (no JAX import at module scope): the
trackers only touch JAX objects handed to them by already-jitted code.
"""

from __future__ import annotations

import contextlib
import threading
import weakref
from typing import Callable, Dict, List, Optional, Tuple


class RetraceViolation(AssertionError):
    """An executable key was traced when the discipline forbids it."""


class RetraceTracker:
    def __init__(self):
        self._mu = threading.Lock()
        # id(jitfn) -> (weakref-or-None, token, last cache size).  The
        # weakref detects id reuse after GC: duplicate-trace keys are
        # scoped per EXECUTABLE CACHE (two scheduler instances tracing
        # the same signature is normal; one cache tracing it twice is
        # eviction or an unstable static), so a recycled id must get a
        # fresh token, not inherit a dead cache's history.
        self._fns: Dict[int, Tuple[Optional[weakref.ref], int, int]] = {}
        self._next_token = 0
        self._seen: Dict[Tuple[str, int, object], int] = {}  # -> trace count
        self._steady = False
        self.traces: List[Tuple[str, bool]] = []   # (label, was_steady)
        self.steady_events: List[str] = []
        self.duplicates: List[str] = []

    def _entry(self, jitfn) -> Tuple[int, int]:
        """(token, last size) for this jit object, id-reuse safe."""
        ent = self._fns.get(id(jitfn))
        if ent is not None and (ent[0] is None or ent[0]() is jitfn):
            return ent[1], ent[2]
        try:
            ref: Optional[weakref.ref] = weakref.ref(jitfn)
        except TypeError:
            ref = None
        token = self._next_token
        self._next_token += 1
        self._fns[id(jitfn)] = (ref, token, 0)
        return token, 0

    # -- recording ---------------------------------------------------------

    def note(self, label: str, jitfn, key_fn: Callable[[], object]) -> None:
        """Record a trace if `jitfn`'s executable cache grew since the
        last note.  key_fn is only evaluated on a cache-size increase."""
        size_of = getattr(jitfn, "_cache_size", None)
        if size_of is None:
            return
        try:
            size = size_of()
        except Exception:  # noqa: BLE001 — observability must not fault
            return
        with self._mu:
            token, prev = self._entry(jitfn)
            ref = self._fns[id(jitfn)][0]
            self._fns[id(jitfn)] = (ref, token, size)
            if size <= prev:
                return
            steady = self._steady
        key = (label, token, key_fn())
        with self._mu:
            n = self._seen.get(key, 0)
            self._seen[key] = n + 1
            self.traces.append((label, steady))
            if n > 0:
                self.duplicates.append(
                    f"executable key for '{label}' traced {n + 1} times "
                    f"(signature {key[2]!r}) — the compile cache is not "
                    "holding this key"
                )
            if steady:
                self.steady_events.append(
                    f"steady-state retrace of '{label}' "
                    f"(signature {key[2]!r}) — a kernel argument escaped "
                    "the pad-bucket lattice"
                )

    # -- steady window -----------------------------------------------------

    def mark_steady(self) -> None:
        """Warmup is over: every later trace is a steady-state recompile."""
        with self._mu:
            self._steady = True

    def clear_steady(self) -> None:
        with self._mu:
            self._steady = False

    # -- results -----------------------------------------------------------

    @property
    def total(self) -> int:
        with self._mu:
            return len(self.traces)

    @property
    def steady_total(self) -> int:
        with self._mu:
            return len(self.steady_events)

    def assert_no_steady_recompiles(self) -> None:
        if self.steady_events:
            raise RetraceViolation("\n".join(self.steady_events[:20]))

    def assert_no_duplicate_traces(self) -> None:
        if self.duplicates:
            raise RetraceViolation("\n".join(self.duplicates[:20]))


_active: Optional[RetraceTracker] = None


@contextlib.contextmanager
def tracked(tracker: Optional[RetraceTracker] = None):
    """Arm retrace tracking for the dynamic extent of the context.
    Nested arming shares the outer tracker (session fixture + per-test
    use must not shadow each other)."""
    global _active
    if _active is not None:
        yield _active
        return
    tracker = tracker or RetraceTracker()
    _active = tracker
    try:
        yield tracker
    finally:
        _active = None


def active() -> Optional[RetraceTracker]:
    return _active


def note(label: str, jitfn, key_fn: Callable[[], object]) -> None:
    """Module-level hook the jit wrappers call: no-op unless a tracker
    is armed (one global None check disarmed)."""
    t = _active
    if t is not None:
        t.note(label, jitfn, key_fn)


def mark_steady() -> None:
    t = _active
    if t is not None:
        t.mark_steady()


def clear_steady() -> None:
    t = _active
    if t is not None:
        t.clear_steady()


def steady_total() -> int:
    t = _active
    return t.steady_total if t is not None else 0


def total() -> int:
    t = _active
    return t.total if t is not None else 0


def signature(tree, statics: tuple = ()) -> tuple:
    """Hashable abstract signature of a pytree of arrays + the static
    args: exactly the pieces that key an XLA executable."""
    import jax

    leaves = jax.tree_util.tree_leaves(tree)
    return (
        tuple(
            (tuple(getattr(l, "shape", ())), str(getattr(l, "dtype", type(l))))
            for l in leaves
        ),
        statics,
    )
