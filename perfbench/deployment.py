"""A configuration file turned into the objects a run creates.

Nodes and pods are plain dictionaries here (the benchmark's own copy of the
upstream templates with a name filled in); the system adapter turns them
into whatever its API takes.  The seed decides only the order in which the
measured pods walk the namespaces: every seed gives the same set of pods.
"""

from __future__ import annotations

import os
import random

import yaml

ZONE_KEY = "topology.kubernetes.io/zone"


def load_template(config: dict, rel: str) -> dict:
    with open(os.path.join(config["_dir"], rel)) as f:
        return yaml.safe_load(f)


def substitute_index(obj, index: int):
    """$index and $index_modN in string values, as upstream's templates use
    them."""
    if isinstance(obj, dict):
        return {k: substitute_index(v, index) for k, v in obj.items()}
    if isinstance(obj, list):
        return [substitute_index(v, index) for v in obj]
    if isinstance(obj, str) and "$index" in obj:
        out = obj
        while "$index_mod" in out:
            pos = out.find("$index_mod") + len("$index_mod")
            end = pos
            while end < len(out) and out[end].isdigit():
                end += 1
            mod = int(out[pos:end]) if end > pos else 1
            out = out[: pos - len("$index_mod")] + str(index % mod) + out[end:]
        return out.replace("$index", str(index))
    return obj


class Deployment:
    """Sizes and templates of one configuration, at full or toy size."""

    def __init__(self, config: dict, toy: bool = False):
        self.config = config
        params = dict(config["test_case"]["workloads"][0]["params"])
        self.capacity_pods = int(config["capacity_pods"])
        if toy:
            over = config["toy"]
            params.update({k: v for k, v in over.items() if k in params})
            self.capacity_pods = int(over["capacity_pods"])
        self.n_nodes = int(params["initNodes"])
        self.n_init_pods = int(params["initPods"])
        self.node_template = load_template(config, config["node_template"])
        self.templates = {
            "init": load_template(config, config["init_pod_template"]),
            "measure": load_template(config, config["measure_pod_template"]),
        }
        assumed = config["assumed"]
        # a count (team-0 ... as PR 24 made them) or the names themselves; and,
        # where upstream's ops put each role's pods in a namespace of its own,
        # the names by role
        names = assumed["namespaces"]
        self.namespaces = (
            [f"team-{i}" for i in range(names)] if isinstance(names, int) else list(names)
        )
        self.role_namespaces = {
            role: list(ns) for role, ns in (assumed.get("role_namespaces") or {}).items()
        }
        # upstream's own word for pods a run does not wait for: the createPods
        # op that creates a role may say skipWaitToCompletion (ops in order:
        # the one that collects metrics creates the measured pods, the one
        # before it the init pods)
        ops = [op for op in config["test_case"]["workloadTemplate"]
               if op.get("opcode") == "createPods"]
        measured = next((op for op in ops if op.get("collectMetrics")), ops[-1])
        by_role = {"measure": measured,
                   "init": next((op for op in ops if op is not measured), {})}
        self.skip_wait = {
            role: bool(op.get("skipWaitToCompletion")) for role, op in by_role.items()
        }
        self.store_args = dict(assumed["store"])
        self.scheduler_args = dict(assumed["scheduler"])
        self.max_fill_share = float(config["max_fill_share"])

    def nodes(self) -> list:
        out = []
        for i in range(self.n_nodes):
            d = substitute_index(self.node_template, i)
            d["metadata"] = dict(d.get("metadata") or {}, name=f"node-{i}")
            out.append(d)
        return out

    def pod(self, role: str, name: str, namespace: str) -> dict:
        t = self.templates[role]
        meta = dict(t.get("metadata") or {}, name=name, namespace=namespace)
        meta.pop("generateName", None)
        return dict(t, metadata=meta)

    def namespace_of(self, role: str) -> str:
        """One namespace the role's pods are created in."""
        return self.role_namespaces.get(role, self.namespaces)[0]

    def namespace_walk(self, seed: int, stream: int, role: str = "measure"):
        """An endless walk over the role's namespaces (all of them, where the
        configuration names none by role) in an order drawn from the seed;
        `stream` separates the warm replay's walk from the window's."""
        rng = random.Random((int(seed) << 3) ^ stream)
        names = self.role_namespaces.get(role, self.namespaces)
        while True:
            order = list(names)
            rng.shuffle(order)
            yield from order
