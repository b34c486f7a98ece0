"""The plain reference: what the configuration's guarantees mean, written
down without the program.

It imports nothing of ``kubernetes_tpu`` and takes nothing the program made
except its answers (the binds the client saw, and what a recovered store reads
back).  Capacities, requests, zones and the spread rule come from the
benchmark's own copy of the templates.

Two uses.  ``Ledger`` replays the binds of a run and counts every breach of a
guarantee; the comparison that decides ``correct`` holds those counts to their
limits.  ``System`` is a straightforward sequential scheduler with a journal
behind the same client interface as the system under test; put in the
program's place, whole, it has to come out correct, and with one guarantee
broken (the control) it has to come out not correct.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time

ZONE_KEY = "topology.kubernetes.io/zone"

_BIN = {"Ki": 2**10, "Mi": 2**20, "Gi": 2**30, "Ti": 2**40}
_DEC = {"k": 10**3, "M": 10**6, "G": 10**9, "T": 10**12}


def quantity(v, cpu: bool = False) -> int:
    """A Kubernetes quantity as an integer: millicores for cpu, else units."""
    s = str(v).strip()
    if cpu:
        return int(s[:-1]) if s.endswith("m") else int(round(float(s) * 1000))
    for suf, mul in _BIN.items():
        if s.endswith(suf):
            return int(float(s[: -len(suf)]) * mul)
    for suf, mul in _DEC.items():
        if s.endswith(suf):
            return int(float(s[: -len(suf)]) * mul)
    return int(float(s))


def pod_requests(template: dict) -> dict:
    req = {"cpu": 0, "memory": 0, "pods": 1}
    for c in (template.get("spec") or {}).get("containers") or []:
        r = ((c.get("resources") or {}).get("requests")) or {}
        req["cpu"] += quantity(r.get("cpu", 0), cpu=True)
        req["memory"] += quantity(r.get("memory", 0))
    return req


def node_allocatable(node: dict) -> dict:
    st = node.get("status") or {}
    a = st.get("allocatable") or st.get("capacity") or {}
    return {
        "cpu": quantity(a.get("cpu", 0), cpu=True),
        "memory": quantity(a.get("memory", 0)),
        "pods": quantity(a.get("pods", 110)),
    }


def spread_rule(template: dict):
    """(topology key, maxSkew, selector labels) of the template's hard spread
    constraint, or None.  The templates here carry at most one.  As in
    Kubernetes, the selector counts matching pods of the incoming pod's own
    namespace only: the rule is held namespace by namespace."""
    for c in (template.get("spec") or {}).get("topologySpreadConstraints") or []:
        if c.get("whenUnsatisfiable", "DoNotSchedule") == "DoNotSchedule":
            sel = ((c.get("labelSelector") or {}).get("matchLabels")) or {}
            return c["topologyKey"], int(c.get("maxSkew", 1)), dict(sel)
    return None


def _matches(template: dict, selector: dict) -> bool:
    labels = (template.get("metadata") or {}).get("labels") or {}
    return all(labels.get(k) == v for k, v in selector.items())


class Ledger:
    """The cluster as the configuration defines it, and a replay of binds
    against it."""

    def __init__(self, nodes: list, templates: dict):
        self.alloc = {n["metadata"]["name"]: node_allocatable(n) for n in nodes}
        self.zone = {
            n["metadata"]["name"]: (n["metadata"].get("labels") or {}).get(ZONE_KEY)
            for n in nodes
        }
        self.req = {role: pod_requests(t) for role, t in templates.items()}
        self.rule = None
        for t in templates.values():
            self.rule = self.rule or spread_rule(t)
        # which roles' pods the spread selector counts
        self.counted = {
            role: self.rule is not None and _matches(t, self.rule[2])
            for role, t in templates.items()
        }
        self.used = {name: {"cpu": 0, "memory": 0, "pods": 0} for name in self.alloc}
        self.zones = sorted({z for z in self.zone.values() if z is not None})
        self.zone_count: dict = {}      # namespace -> {zone: counted pods}
        self.unknown_node = 0
        self.max_skew_seen = 0

    def counts(self, namespace: str) -> dict:
        """The counted pods of one namespace, zone by zone (every zone of the
        cluster is a domain, an empty one too)."""
        c = self.zone_count.get(namespace)
        if c is None:
            c = self.zone_count[namespace] = dict.fromkeys(self.zones, 0)
        return c

    def bind(self, role: str, node: str, namespace: str) -> None:
        used = self.used.get(node)
        if used is None:
            self.unknown_node += 1
            return
        for k, v in self.req[role].items():
            used[k] += v
        if self.counted[role] and self.zone[node] is not None:
            self.counts(namespace)[self.zone[node]] += 1

    def mark_wave_end(self) -> None:
        for c in self.zone_count.values():
            self.max_skew_seen = max(self.max_skew_seen, max(c.values()) - min(c.values()))

    def overcommitted(self) -> list:
        return [
            name for name, used in self.used.items()
            if any(used[k] > self.alloc[name][k] for k in used)
        ]


# -- the reference put in the program's place ------------------------------------

BREAKS = ("capacity", "skew", "durability", "once")


class _Watch:
    def __init__(self):
        self._q = collections.deque()
        self._cv = threading.Condition()
        self.expired = False

    def put(self, ev) -> None:
        with self._cv:
            self._q.append(ev)
            self._cv.notify()

    def get(self, timeout: float):
        with self._cv:
            if not self._q:
                self._cv.wait(timeout)
            return self._q.popleft() if self._q else None

    def relist(self):
        return {}

    def stop(self) -> None:
        pass


class System:
    """A sequential scheduler over the Ledger's own arithmetic: pods in
    arrival order, each to the next node (round robin) where it fits and,
    for a pod under the spread rule, whose zone keeps the skew of its
    namespace's counted pods within maxSkew.  Binds are journaled as JSON lines and flushed on close.

    `broken` names the guarantee a control run breaks: "capacity" ignores
    allocatable and stacks pods on a thousandth of the nodes; "skew" ignores the
    spread rule and uses one zone's nodes; "durability" leaves every 97th
    acknowledged bind out of the journal; "once" later moves every 101st
    bound pod to another node.
    """

    def __init__(self, deployment, workdir: str, recorder, broken: str | None = None):
        if broken is not None and broken not in BREAKS:
            raise ValueError(f"unknown control {broken!r}; one of {BREAKS}")
        self.dep = deployment
        self.rec = recorder
        self.broken = broken
        self.journal = os.path.join(workdir, "reference.jsonl")
        self._pending = collections.deque()
        self._cv = threading.Condition()
        self._watches: list = []
        self._stop = threading.Event()
        self._rv = 0
        self._thread = threading.Thread(target=self._run, name="reference-sched", daemon=True)

    def start(self) -> None:
        self._nodes = self.dep.nodes()
        self._ledger = Ledger(self._nodes, self.dep.templates)
        self._names = [n["metadata"]["name"] for n in self._nodes]
        self._cursor = 0
        self._bound = 0
        self._f = open(self.journal, "w")
        self._thread.start()

    def warmup(self, pods: list) -> None:
        pass

    def counters(self) -> dict:
        return {}

    def watch(self) -> _Watch:
        w = _Watch()
        self._watches.append(w)
        return w

    def create(self, pod: dict, role: str) -> None:
        m = pod["metadata"]
        with self._cv:
            self._rv += 1
            self._emit(("ADDED", m["namespace"], m["name"], "", self._rv))
            self._pending.append((m["namespace"], m["name"], role))
            self._cv.notify()

    def _emit(self, ev) -> None:
        for w in self._watches:
            w.put(ev)

    def _fits(self, role: str, node: str, namespace: str) -> bool:
        led = self._ledger
        if self.broken != "capacity":
            used, alloc = led.used[node], led.alloc[node]
            if any(used[k] + v > alloc[k] for k, v in led.req[role].items()):
                return False
        if led.counted[role] and self.broken != "skew":
            c = led.counts(namespace)
            if c[led.zone[node]] + 1 - min(c.values()) > led.rule[1]:
                return False
        return True

    def _pick(self, role: str, namespace: str):
        names = self._names
        n = len(names)
        if self.broken == "capacity":
            n = max(1, n // 1000)
        for _ in range(len(names)):
            node = names[self._cursor % n]
            self._cursor += 1
            if self.broken == "skew" and self._ledger.counted[role] \
                    and self._ledger.zone[node] != self._ledger.zone[names[0]]:
                continue
            if self._fits(role, node, namespace):
                return node
        return None

    def _run(self) -> None:
        moved = []
        while not self._stop.is_set():
            with self._cv:
                if not self._pending:
                    self._cv.wait(0.05)
                batch = [self._pending.popleft() for _ in range(min(len(self._pending), 1024))]
            if not batch:
                continue
            lines = []
            now = self.rec.clock()
            self.rec.cycles.append({
                "keys": [(ns, name) for ns, name, _ in batch], "pods": len(batch),
                "t_dispatch0": now, "t_decode1": now, "route": "reference",
            })
            for ns, name, role in batch:
                node = self._pick(role, ns)
                if node is None:
                    continue    # stays unbound: the cluster is full
                self._ledger.bind(role, node, ns)
                self._bound += 1
                if not (self.broken == "durability" and self._bound % 97 == 0):
                    lines.append(json.dumps([ns, name, node]))
                if self.broken == "once" and self._bound % 101 == 0:
                    moved.append((ns, name, role, node))
                with self._cv:
                    self._rv += 1
                    self._emit(("MODIFIED", ns, name, node, self._rv))
            for ns, name, role, node in moved:
                other = self._names[(self._names.index(node) + 1) % len(self._names)]
                lines.append(json.dumps([ns, name, other]))
                with self._cv:
                    self._rv += 1
                    self._emit(("MODIFIED", ns, name, other, self._rv))
            moved.clear()
            self._f.write("\n".join(lines) + "\n")
            # the next batch: binds reach the client in waves, at most a
            # thousand pods a second, so that no run of it fills the cluster
            time.sleep(max(0.06, 0.001 * len(batch)))

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=30.0)
        self._f.flush()
        os.fsync(self._f.fileno())
        self._f.close()

    def recover(self) -> dict:
        out = {}
        with open(self.journal) as f:
            for line in f:
                if line.strip():
                    ns, name, node = json.loads(line)
                    out[(ns, name)] = node
        return out
