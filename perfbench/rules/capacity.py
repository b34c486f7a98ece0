"""No node holds more than its allocatable cpu, memory or pods, at any bind.

Requests come from the benchmark's copy of the pod templates, allocatable from
its copy of the node template.  A deletion frees its room from its
resourceVersion on: the replay calls ``unbind`` there, so a slot reused before
its delete is a breach and after it is none.
"""

from __future__ import annotations

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


class Rule:
    control = "capacity"
    held = "every_bind"

    @staticmethod
    def claims(kind: str, constraint) -> bool:
        return False    # requests are no listed constraint: they bind every pod

    @staticmethod
    def applies(templates: dict) -> bool:
        return True

    def __init__(self, nodes: list, templates: dict):
        self.alloc = {n["metadata"]["name"]: node_allocatable(n) for n in nodes}
        self.req = {role: pod_requests(t) for role, t in templates.items()}
        self.used = {name: {"cpu": 0, "memory": 0, "pods": 0} for name in self.alloc}
        self.over: set = set()      # nodes that some bind left over their allocatable

    def admits(self, role: str, node: str, namespace: str) -> bool:
        used, alloc = self.used[node], self.alloc[node]
        return not any(used[k] + v > alloc[k] for k, v in self.req[role].items())

    def bind(self, role: str, node: str, namespace: str) -> None:
        used, alloc = self.used[node], self.alloc[node]
        for k, v in self.req[role].items():
            used[k] += v
        if any(used[k] > alloc[k] for k in used):
            self.over.add(node)

    def unbind(self, role: str, node: str, namespace: str) -> None:
        used = self.used[node]
        for k, v in self.req[role].items():
            used[k] -= v

    def mark_wave_end(self) -> None:
        pass

    def checks(self) -> dict:
        return {"overcommitted_nodes": [len(self.over), 0]}

    def control_nodes(self, names: list, role: str):
        """The control stacks every pod on a thousandth of the nodes."""
        return names[: max(1, len(names) // 1000)]
