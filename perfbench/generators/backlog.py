"""Closed loop: keep `backlog_pods` created-and-unbound pods outstanding,
topping up as the client sees binds.

That is a controller replacing what was scheduled.  What the rate means is
the mix's to say: with `backlog_pods` at several batches every cycle can pop a
full batch and pods bound per second are the system's rate at saturation (as
upstream scheduler_perf measures SchedulingThroughput, the measured pods
there before the scheduler gets to them); with fewer outstanding than one
batch the rate is `backlog_pods` over the loop's latency, create to observed
bind, and `backlog_depth_min.backlog` reads under a batch by design.

One thread keeps the count and hands out chunks of named pods, in an order
that the seed alone decides; `creators` threads make the calls, as a
controller's workers do.  (One caller alone gives up the interpreter at every
journal write and waits to get it back, and so creates more slowly than the
scheduler binds.)

With `live_pods` the pods complete, as a Job's do: once more than that many
are bound and not deleted, the same loop hands the same worker threads the
longest-bound ones to delete (set-up's pods first: they are the oldest), a
chunk at a time.  The population the scheduler places into then stays at
`live_pods` however long the run.  A deletion is load, not result: throughput
is still binds over whole waves.  Without the parameter nothing is deleted.
"""

from __future__ import annotations

import queue
import threading
import time


class Generator:
    kind = "backlog"

    def __init__(self, params, deployment, system, client, recorder, seed):
        self.p = params
        self.dep = deployment
        self.system = system
        self.client = client
        self.rec = recorder
        self.target = int(params["backlog_pods"])
        self.chunk = int(params.get("topup_chunk", 64))
        self.live_pods = params.get("live_pods")    # None: pods never complete
        self.primed = False          # the backlog has been full once
        self.created: list = []      # (ns, name, role, t_issued, due)
        self.depth: list = []        # (t, created - bound) samples
        self.deleted: list = []      # (ns, name, t_acknowledged)
        self.live: list = []         # (t, bound and not handed out for deletion, bound and not seen deleted)
        self._doomed = 0             # pods of the client's bind log handed out for deletion
        self._walk = deployment.namespace_walk(seed, 1)
        self._window_walk = deployment.namespace_walk(seed, 2)
        self._prefix = "warm"
        self._issued = 0
        self._bound0 = client.n_bound()     # set-up's own pods, all bound by now
        self._tasks: queue.Queue = queue.Queue()
        self._stop = threading.Event()
        self._threads = [threading.Thread(target=self._run, name="perfbench-gen", daemon=True)]
        self._threads += [
            threading.Thread(target=self._create, name=f"perfbench-create-{i}", daemon=True)
            for i in range(int(params.get("creators", 1)))
        ]

    def start(self) -> None:
        for t in self._threads:
            t.start()

    def open_window(self, seconds: float) -> float:
        """The load stays on; from here on pods take the window's names and
        the window's walk over the namespaces."""
        self._prefix, self._walk = "pod", self._window_walk
        return self.rec.clock()

    def close_window(self, t_close: float) -> None:
        pass    # the load stays on until the closing wave has begun

    def stop(self) -> None:
        self._stop.set()
        for t in self._threads:
            t.join(timeout=60.0)

    def due_in_window(self, t_open: float, t_close: float) -> list:
        return []   # a closed loop has no schedule

    def _run(self) -> None:
        clock = self.rec.clock
        while not self._stop.is_set():
            bound = self.client.n_bound() - self._bound0
            self.depth.append((clock(), len(self.created) - bound))
            if self.live_pods is not None:
                self._complete(clock())
            need = self.target - (self._issued - bound)
            if need < self.chunk:
                self.primed = self.primed or need <= 0
                time.sleep(0.002)
                continue
            while need >= self.chunk:
                prefix, base, walk = self._prefix, self._issued, self._walk
                self._tasks.put(("create", [
                    self.dep.pod("measure", f"{prefix}-{base + i}", next(walk))
                    for i in range(self.chunk)
                ]))
                self._issued += self.chunk
                need -= self.chunk

    def _complete(self, now: float) -> None:
        """Hand out the longest-bound pods while more than `live_pods` live."""
        live = self.client.n_bound() - self._doomed
        while live - int(self.live_pods) >= self.chunk:
            oldest = self.client.bind_log_since(self._doomed, self.chunk)
            self._tasks.put(("delete", [(ns, name) for _, _, ns, name, _ in oldest]))
            self._doomed += self.chunk
            live -= self.chunk
        self.live.append((now, live, self.client.n_live()))

    def _create(self) -> None:
        clock, system, created = self.rec.clock, self.system, self.created
        while not self._stop.is_set():
            try:
                what, pods = self._tasks.get(timeout=0.05)
            except queue.Empty:
                continue
            if what == "delete":
                for ns, name in pods:
                    system.delete(ns, name)
                    self.deleted.append((ns, name, clock()))
                continue
            with self.rec.span("store_create", len(pods)):
                for d in pods:
                    system.create(d, "measure")
                    m = d["metadata"]
                    created.append((m["namespace"], m["name"], "measure", clock(), None))
