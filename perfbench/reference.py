"""The plain reference: what the configuration's guarantees mean, written
down without the program.

It imports nothing of ``kubernetes_tpu`` and takes nothing the program made
except its answers (the binds and deletions the client saw, and what a
recovered store reads back).  The deployment's hard rules (allocatable, a zone
spread, a pod anti-affinity) are one file each under ``rules/``, read off the
benchmark's own copy of the templates; none of their arithmetic is here.

Two uses.  ``Ledger`` replays the binds and deletions of a run against the
rules that apply and counts every breach of a guarantee; the comparison that
decides ``correct`` holds those counts to their limits.  ``System`` is a
straightforward sequential scheduler with a journal behind the same client
interface as the system under test; put in the program's place, whole, it has
to come out correct, and with one guarantee broken (the control) it has to
come out not correct.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time

from . import rules


class Ledger:
    """The cluster as the configuration defines it: the rules that apply to
    its templates, and a replay of binds and deletions against them.  `held`
    names the rules a replay drives, by when they are read ("every_bind",
    "whole_solves"); None drives them all."""

    def __init__(self, nodes: list, templates: dict):
        self.nodes = {n["metadata"]["name"] for n in nodes}
        self.rules = rules.applicable(nodes, templates)

    def _held(self, held):
        return [r for r in self.rules if held is None or r.held == held]

    def bind(self, role: str, node: str, namespace: str, held=None) -> None:
        if node in self.nodes:
            for r in self._held(held):
                r.bind(role, node, namespace)

    def unbind(self, role: str, node: str, namespace: str, held=None) -> None:
        if node in self.nodes:
            for r in self._held(held):
                r.unbind(role, node, namespace)

    def mark_wave_end(self) -> None:
        for r in self.rules:
            r.mark_wave_end()

    def placeable(self, role: str, namespace: str) -> bool:
        """Does every rule that applies admit such a pod on some node of the
        cluster as the ledger holds it now?  Asked of a fresh ledger, the
        empty cluster, it says whether the role can be bound at all: a role
        that no node can hold then is not meant to bind, whatever a run does."""
        return any(
            all(r.admits(role, node, namespace) for r in self.rules) for node in self.nodes
        )

    def checks(self) -> dict:
        """Every rule's numbers, each beside its limit."""
        out = {}
        for r in self.rules:
            out.update(r.checks())
        return out


def unplaceable_roles(deployment, ledger: Ledger | None = None) -> frozenset:
    """The roles whose pods no node of the empty cluster admits, by the
    deployment's rules alone (`ledger`: a fresh one, where the caller has it)."""
    ledger = ledger or Ledger(deployment.nodes(), deployment.templates)
    return frozenset(
        role for role in deployment.templates
        if not ledger.placeable(role, deployment.namespace_of(role))
    )


# -- the reference put in the program's place ------------------------------------

# the controls that break no rule of placement but the store's own guarantees
STORE_BREAKS = ("durability", "delete_durability", "delete_lost", "once")


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
        return {}, 0

    def stop(self) -> None:
        pass


class System:
    """A sequential scheduler over the rules' own arithmetic: pods in arrival
    order, each to the next node (round robin) that every rule of the
    deployment admits; a pod that no node admits waits for the next cycle.
    Binds and deletions are journaled as JSON lines and flushed on close.

    `broken` names the guarantees a control run breaks (one, or several with
    commas between).  A rule's own control ignores that rule and picks from
    the nodes the rule file names, so that the breach is sure: "capacity"
    stacks pods on a thousandth of the nodes, "skew" uses one zone's nodes,
    "antiaffinity" a tenth of the nodes.  The store's: "durability" leaves
    every 97th acknowledged bind out of the journal, "delete_durability" every
    97th acknowledged deletion; "delete_lost" acknowledges every 97th deletion
    and does nothing (no event, no journal line, the pod lives on); "once"
    later moves every 101st bound pod to another node.

    Pods of a role that no node of the empty cluster admits are parked: each
    is acknowledged, emitted as ADDED and journaled with an empty node, and no
    cycle walks the nodes for it.  Where the deployment has such a role,
    "unplaceable" binds every 97th of them to the next node all the same.
    """

    def __init__(self, deployment, workdir: str, recorder, broken: str | None = None):
        self.dep = deployment
        self.rec = recorder
        self.broken = frozenset(broken.split(",")) if broken else frozenset()
        self.journal = os.path.join(workdir, "reference.jsonl")
        self._pending = collections.deque()
        self._cv = threading.Condition()
        self._mu = threading.Lock()     # the ledger, the journal file, who is where
        self._watches: list = []
        self._stop = threading.Event()
        self._rv = 0
        self._thread = threading.Thread(target=self._run, name="reference-sched", daemon=True)

    def start(self) -> None:
        self._nodes = self.dep.nodes()
        self._ledger = Ledger(self._nodes, self.dep.templates)
        self._unplaceable = unplaceable_roles(self.dep, self._ledger)
        known = set(STORE_BREAKS) | {r.control for r in self._ledger.rules}
        if self._unplaceable:
            known.add("unplaceable")
        if self.broken - known:
            raise ValueError(
                f"unknown control {sorted(self.broken - known)}; this deployment has {sorted(known)}"
            )
        self._names = [n["metadata"]["name"] for n in self._nodes]
        # the nodes each role's pods are picked from: all, but for a control
        self._from = {}
        for r in self._ledger.rules:
            if r.control not in self.broken:
                continue
            for role in self.dep.templates:
                names = r.control_nodes(self._names, role)
                if names is not None and len(names) < len(self._from.get(role, self._names)):
                    self._from[role] = names
        self._where: dict = {}      # (namespace, name) -> (role, node) of the live bound pods
        self._cursor = 0
        self._bound = 0
        self._deleted = 0
        self._parked = 0
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
        if role in self._unplaceable:
            return self._park(m["namespace"], m["name"], role)
        with self._cv:
            self._rv += 1
            self._emit(("ADDED", m["namespace"], m["name"], "", self._rv))
            self._pending.append((m["namespace"], m["name"], role))
            self._cv.notify()

    def _park(self, ns: str, name: str, role: str) -> None:
        """A pod no node can hold: acknowledged, durable, and left pending."""
        with self._mu:
            self._parked += 1
            self._f.write(json.dumps([ns, name, ""]) + "\n")
            with self._cv:
                self._rv += 1
                self._emit(("ADDED", ns, name, "", self._rv))
            if "unplaceable" in self.broken and self._parked % 97 == 0:
                node = self._names[self._cursor % len(self._names)]
                self._cursor += 1
                self._ledger.bind(role, node, ns)
                self._where[(ns, name)] = (role, node)
                self._f.write(json.dumps([ns, name, node]) + "\n")
                with self._cv:
                    self._rv += 1
                    self._emit(("MODIFIED", ns, name, node, self._rv))

    def delete(self, namespace: str, name: str) -> None:
        """A bound pod completes: its room is free from here on."""
        with self._mu:
            self._deleted += 1
            if "delete_lost" in self.broken and self._deleted % 97 == 0:
                return      # acknowledged, and lost whole
            role, node = self._where.pop((namespace, name))
            self._ledger.unbind(role, node, namespace)
            if not ("delete_durability" in self.broken and self._deleted % 97 == 0):
                self._f.write(json.dumps([namespace, name, None]) + "\n")
            with self._cv:
                self._rv += 1
                self._emit(("DELETED", namespace, name, node, self._rv))

    def _emit(self, ev) -> None:
        for w in self._watches:
            w.put(ev)

    def _fits(self, role: str, node: str, namespace: str) -> bool:
        return all(
            r.admits(role, node, namespace)
            for r in self._ledger.rules if r.control not in self.broken
        )

    def _pick(self, role: str, namespace: str):
        names = self._from.get(role, self._names)
        for _ in range(len(names)):
            node = names[self._cursor % len(names)]
            self._cursor += 1
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
            with self._mu:
                for ns, name, role in batch:
                    node = self._pick(role, ns)
                    if node is None:
                        with self._cv:      # no room now: a completion may make some
                            self._pending.append((ns, name, role))
                        continue
                    self._ledger.bind(role, node, ns)
                    self._where[(ns, name)] = (role, node)
                    self._bound += 1
                    if not ("durability" in self.broken and self._bound % 97 == 0):
                        lines.append(json.dumps([ns, name, node]))
                    if "once" in self.broken and self._bound % 101 == 0:
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
                if lines:
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
                    if node is None:
                        out.pop((ns, name), None)
                    else:
                        out[(ns, name)] = node
        return out
