"""The client's side of a run: its own Pod watch, and what it learned there.

A bind is known when this thread reads an event whose pod carries a node
name.  (The store may fold an unread ADDED and the bind that followed into
one ADDED event; that is a bind all the same.)  Where the watch expires the
client relists, as a real client does, and times the binds it learns that
way at the relist.
"""

from __future__ import annotations

import threading
import time


class WatchClient:
    def __init__(self, system, clock=time.perf_counter):
        self.clock = clock
        self._watch = system.watch()
        self._mu = threading.Lock()
        self.bound: dict = {}        # (ns, name) -> (t, node, rv)
        self.bind_log: list = []     # (t, rv, ns, name, node) in arrival order
        self.rebound: list = []      # (key, first node, other node)
        self.gone: dict = {}         # (ns, name) -> (t, rv): bound pods seen deleted
        self.gone_pending: dict = {}     # (ns, name) -> (t, rv): pods seen deleted unbound
        self.rv_regressions = 0
        self.events = 0
        self.expired = 0
        self.relists: list = []      # (t0, t1, learned)
        self._last_rv: dict = {}     # namespace -> newest rv seen
        self._max_rv = 0             # newest rv seen on the watch at all
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, name="perfbench-watch", daemon=True)

    def start(self) -> "WatchClient":
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=30.0)
        self._watch.stop()

    # -- reads (any thread) --------------------------------------------------

    def n_bound(self) -> int:
        """Pods ever seen bound, the deleted among them."""
        return len(self.bound)

    def n_live(self) -> int:
        """Pods seen bound and not seen deleted."""
        return len(self.bound) - len(self.gone)

    def bind_log_since(self, i: int, n: int | None = None) -> list:
        """The log from entry `i` on, or its next `n` entries."""
        with self._mu:
            return self.bind_log[i:] if n is None else self.bind_log[i:i + n]

    # -- the watch thread ----------------------------------------------------

    def _learn(self, t, rv, ns, name, node) -> None:
        key = (ns, name)
        seen = self.bound.get(key)
        if seen is None:
            with self._mu:
                self.bound[key] = (t, node, rv)
                self.bind_log.append((t, rv, ns, name, node))
        elif seen[1] != node:
            self.rebound.append((key, seen[1], node))

    def _run(self) -> None:
        w = self._watch
        while not self._stop.is_set():
            ev = w.get(0.2)
            if ev is None:
                if w.expired:
                    self.expired += 1
                    t0 = self.clock()
                    listed, list_rv = w.relist()
                    t1 = self.clock()
                    learned = 0
                    # a bind learned here happened at or before the list's
                    # rv, a deletion after the last event read: the replay
                    # then frees no room too late and none too early
                    for (ns, name), node in listed.items():
                        if (ns, name) not in self.bound:
                            learned += 1
                        self._learn(t1, list_rv, ns, name, node)
                    for key in self.bound:
                        if key not in listed and key not in self.gone:
                            self.gone[key] = (t1, self._max_rv)
                    self._last_rv.clear()
                    self.relists.append((t0, t1, learned))
                continue
            kind, ns, name, node, rv = ev
            self.events += 1
            if rv <= self._last_rv.get(ns, 0):
                self.rv_regressions += 1
            self._last_rv[ns] = rv
            self._max_rv = max(self._max_rv, rv)
            if node:
                self._learn(self.clock(), rv, ns, name, node)
                if kind == "DELETED":
                    self.gone.setdefault((ns, name), (self.clock(), rv))
            elif kind == "DELETED":
                self.gone_pending.setdefault((ns, name), (self.clock(), rv))
