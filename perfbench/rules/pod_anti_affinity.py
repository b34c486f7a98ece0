"""Required pod anti-affinity: at no instant do two live pods share a topology
domain where either's term repels the other.

From the templates' ``requiredDuringSchedulingIgnoredDuringExecution`` terms
under ``podAntiAffinity``.  A term repels the pods its ``matchLabels`` select
in the namespaces it lists (the owner's own where it lists none), within one
domain of its ``topologyKey``: for ``kubernetes.io/hostname`` the node, for any
other key the nodes that carry the same value of that label.  Kubernetes holds
the incoming pod's terms against the pods that are there and the terms of the
pods that are there against the incomer, so a bind is a breach either way
round.  Live means bound and not deleted: ``unbind`` takes a pod out of its
domains from its deletion's resourceVersion on.
"""

from __future__ import annotations

HOSTNAME_KEY = "kubernetes.io/hostname"
_TERM_KEYS = {"labelSelector", "topologyKey", "namespaces"}


def required_terms(template: dict) -> list:
    """[(topology key, selector labels, namespaces or None)]"""
    anti = ((template.get("spec") or {}).get("affinity") or {}).get("podAntiAffinity") or {}
    out = []
    for term in anti.get("requiredDuringSchedulingIgnoredDuringExecution") or []:
        sel = ((term.get("labelSelector") or {}).get("matchLabels")) or {}
        out.append((term["topologyKey"], dict(sel), list(term.get("namespaces") or []) or None))
    return out


def _labels(template: dict) -> dict:
    return (template.get("metadata") or {}).get("labels") or {}


class Rule:
    control = "antiaffinity"
    held = "every_bind"

    @staticmethod
    def claims(kind: str, constraint) -> bool:
        # a namespaceSelector or matchExpressions is more than this file reads
        return kind == "podAntiAffinity" and set(constraint) <= _TERM_KEYS \
            and set(constraint.get("labelSelector") or {}) <= {"matchLabels"}

    @staticmethod
    def applies(templates: dict) -> bool:
        return any(required_terms(t) for t in templates.values())

    def __init__(self, nodes: list, templates: dict):
        terms = {role: required_terms(t) for role, t in templates.items()}
        self.keys = sorted({key for ts in terms.values() for key, _, _ in ts})
        self.domain = {
            key: {
                n["metadata"]["name"]: n["metadata"]["name"] if key == HOSTNAME_KEY
                else (n["metadata"].get("labels") or {}).get(key)
                for n in nodes
            } for key in self.keys
        }
        # repels[(a, b)]: the terms of role a whose selector takes role b's
        # labels, as (topology key, namespaces or None)
        self.repels = {
            (a, b): [
                (key, namespaces) for key, sel, namespaces in terms[a]
                if all(_labels(templates[b]).get(k) == v for k, v in sel.items())
            ] for a in templates for b in templates
        }
        self.live = {key: {} for key in self.keys}   # key -> domain -> {(role, namespace): pods}
        self.colocated = 0

    def _repelled(self, role: str, node: str, namespace: str) -> bool:
        for key in self.keys:
            there = self.live[key].get(self.domain[key][node])
            if not there:
                continue
            for other, other_ns in there:
                for k, namespaces in self.repels[(role, other)]:       # mine against theirs
                    if k == key and other_ns in (namespaces or (namespace,)):
                        return True
                for k, namespaces in self.repels[(other, role)]:       # theirs against me
                    if k == key and namespace in (namespaces or (other_ns,)):
                        return True
        return False

    def admits(self, role: str, node: str, namespace: str) -> bool:
        return not self._repelled(role, node, namespace)

    def bind(self, role: str, node: str, namespace: str) -> None:
        if self._repelled(role, node, namespace):
            self.colocated += 1
        for key in self.keys:
            d = self.domain[key][node]
            if d is not None:
                there = self.live[key].setdefault(d, {})
                there[(role, namespace)] = there.get((role, namespace), 0) + 1

    def unbind(self, role: str, node: str, namespace: str) -> None:
        for key in self.keys:
            there = self.live[key].get(self.domain[key][node])
            if there and (role, namespace) in there:
                there[(role, namespace)] -= 1
                if not there[(role, namespace)]:
                    del there[(role, namespace)]

    def mark_wave_end(self) -> None:
        pass

    def checks(self) -> dict:
        return {"colocated_pods": [self.colocated, 0]}

    def control_nodes(self, names: list, role: str):
        """The control packs the pods onto a tenth of the nodes, where
        allocatable still holds them and the rule cannot."""
        return names[: max(1, len(names) // 10)]
