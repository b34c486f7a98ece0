"""Open loop: single pods at Poisson times, at a rate fixed in the traffic
file, whatever the system does.

Every seed sends the same work.  The window's gaps between arrivals are one
set, drawn once from `schedule_seed` and scaled so that exactly
rate x seconds pods fall due inside the window; the run's seed only puts them
in another order.  The warm replay before the window draws its own gaps from
another stream of the seed.  A pod's latency is counted from the time it was
due, so a generator that ran late (reported beside it) cannot hide a stall.
"""

from __future__ import annotations

import random
import threading
import time


def window_schedule(rate: float, seconds: float, schedule_seed: int, seed: int) -> list:
    """Offsets from the window's opening at which pods fall due."""
    n = max(1, int(round(rate * seconds)))
    base = random.Random(int(schedule_seed))
    gaps = [base.expovariate(rate) for _ in range(n)]
    scale = seconds * n / (n + 1.0) / sum(gaps)
    gaps = [g * scale for g in gaps]
    random.Random((int(seed) << 3) ^ 5).shuffle(gaps)
    out, t = [], 0.0
    for g in gaps:
        t += g
        out.append(t)
    return out


class Generator:
    kind = "open_loop"

    def __init__(self, params, deployment, system, client, recorder, seed):
        self.p = params
        self.dep = deployment
        self.system = system
        self.rec = recorder
        self.seed = seed
        self.rate = float(params["rate_pods_per_s"])
        self.created: list = []      # (ns, name, role, t_issued, due)
        self.depth: list = []
        self.primed = True
        self._replay_rng = random.Random((int(seed) << 3) ^ 3)
        self._walk = deployment.namespace_walk(seed, 1)
        self._window_walk = deployment.namespace_walk(seed, 2)
        self._mu = threading.Lock()
        self._window = None          # (t_open, [offsets])
        self._n_replay = None        # how many of `created` the replay sent
        self._sent_all = threading.Event()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, name="perfbench-gen", daemon=True)

    def start(self) -> None:
        self._thread.start()

    def open_window(self, seconds: float) -> float:
        offsets = window_schedule(self.rate, seconds, self.p["schedule_seed"], self.seed)
        with self._mu:
            t_open = self.rec.clock()
            self._window = (t_open, offsets)
        return t_open

    def close_window(self, t_close: float) -> None:
        """Every offset lies inside the window, but a sender that ran late is
        still sending: the schedule never waits for the system, and no pod
        that fell due is dropped."""
        self._sent_all.wait(timeout=float(self.p["drain_s"]))

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=60.0)

    def due_in_window(self, t_open: float, t_close: float) -> list:
        # the schedule's own pods; the replay's last pod may fall due a
        # moment after the opening and is not one of them
        sent = [c for c in self.created[self._n_replay:] if t_open <= c[4] < t_close]
        # what was never sent (the sender was stopped first) fell due all the
        # same: no issue time, never bound
        _, offsets = self._window
        unsent = [("", f"unsent-{i}", "measure", None, t_open + off)
                  for i, off in enumerate(offsets[len(sent):], len(sent))]
        return sent + unsent

    def _send(self, prefix: str, walk, due: float) -> None:
        clock = self.rec.clock
        wait = due - clock()
        if wait > 0:
            time.sleep(wait)
        d = self.dep.pod("measure", f"{prefix}-{len(self.created)}", next(walk))
        t = clock()
        with self.rec.span("store_create", 1):
            self.system.create(d, "measure")
        m = d["metadata"]
        self.created.append((m["namespace"], m["name"], "measure", t, due))

    def _run(self) -> None:
        due = self.rec.clock()
        while not self._stop.is_set():
            with self._mu:
                window = self._window
            if window is not None:
                break
            due += self._replay_rng.expovariate(self.rate)
            self._send("warm", self._walk, due)
        if window is None:
            return
        t_open, offsets = window
        self._n_replay = len(self.created)
        for off in offsets:
            if self._stop.is_set():
                return
            self._send("pod", self._window_walk, t_open + off)
        self._sent_all.set()
