"""Required pod affinity: at every bind, the incoming pod finds a live pod
that each of its terms selects in the node's domain of that term's
topologyKey, or it is the first of its group.

From the templates' ``requiredDuringSchedulingIgnoredDuringExecution`` terms
under ``podAffinity``.  A term selects the pods its ``matchLabels`` take in
the namespaces it lists (the incomer's own where it lists none); a domain is
the node for ``kubernetes.io/hostname`` and, for any other key, the nodes that
carry the same value of that label.  A node without a term's key admits
nothing.  Upstream's first-pod escape: where no live pod in any domain of its
key matches any term of the incomer, and the incomer matches every term of
its own, any node with the keys admits it.  Live means bound and not
deleted; a deletion breaks no pod already bound (IgnoredDuringExecution), it
only leaves later binds fewer pods to land beside.
"""

from __future__ import annotations

HOSTNAME_KEY = "kubernetes.io/hostname"
_TERM_KEYS = {"labelSelector", "topologyKey", "namespaces"}


def required_terms(template: dict) -> list:
    """[(topology key, selector labels, namespaces or None)]"""
    aff = ((template.get("spec") or {}).get("affinity") or {}).get("podAffinity") or {}
    out = []
    for term in aff.get("requiredDuringSchedulingIgnoredDuringExecution") or []:
        sel = ((term.get("labelSelector") or {}).get("matchLabels")) or {}
        out.append((term["topologyKey"], dict(sel), list(term.get("namespaces") or []) or None))
    return out


def _labels(template: dict) -> dict:
    return (template.get("metadata") or {}).get("labels") or {}


class Rule:
    control = "affinity"
    held = "every_bind"

    @staticmethod
    def claims(kind: str, constraint) -> bool:
        # a namespaceSelector or matchExpressions is more than this file reads
        return kind == "podAffinity" and set(constraint) <= _TERM_KEYS \
            and set(constraint.get("labelSelector") or {}) <= {"matchLabels"}

    @staticmethod
    def applies(templates: dict) -> bool:
        return any(required_terms(t) for t in templates.values())

    def __init__(self, nodes: list, templates: dict):
        self.terms = {role: required_terms(t) for role, t in templates.items()}
        self.keys = sorted({key for ts in self.terms.values() for key, _, _ in ts})
        self.domain = {
            key: {
                n["metadata"]["name"]: n["metadata"]["name"] if key == HOSTNAME_KEY
                else (n["metadata"].get("labels") or {}).get(key)
                for n in nodes
            } for key in self.keys
        }
        # selects[role][i]: the roles whose labels term i of `role` takes
        self.selects = {
            role: [
                {b for b, tb in templates.items()
                 if all(_labels(tb).get(k) == v for k, v in sel.items())}
                for _, sel, _ in ts
            ] for role, ts in self.terms.items()
        }
        self.live = {key: {} for key in self.keys}   # key -> domain -> {(role, namespace): pods}
        self.total = {key: {} for key in self.keys}  # key -> {(role, namespace): pods} in any domain
        self.unaffine = 0

    def _selected(self, there: dict, roles: set, namespaces) -> bool:
        return any(there.get((role, ns)) for role in roles for ns in namespaces)

    def admits(self, role: str, node: str, namespace: str) -> bool:
        terms = self.terms[role]
        if not terms:
            return True
        if any(self.domain[key][node] is None for key, _, _ in terms):
            return False
        spaces = [namespaces or (namespace,) for _, _, namespaces in terms]
        if all(
            self._selected(self.live[key].get(self.domain[key][node], {}), roles, ns)
            for (key, _, _), roles, ns in zip(terms, self.selects[role], spaces)
        ):
            return True
        # the first pod of a group: nothing matches in any domain, and it matches itself
        return not any(
            self._selected(self.total[key], roles, ns)
            for (key, _, _), roles, ns in zip(terms, self.selects[role], spaces)
        ) and all(
            role in roles and namespace in ns
            for roles, ns in zip(self.selects[role], spaces)
        )

    def bind(self, role: str, node: str, namespace: str) -> None:
        if not self.admits(role, node, namespace):
            self.unaffine += 1
        for key in self.keys:
            d = self.domain[key][node]
            if d is not None:
                for there in (self.live[key].setdefault(d, {}), self.total[key]):
                    there[(role, namespace)] = there.get((role, namespace), 0) + 1

    def unbind(self, role: str, node: str, namespace: str) -> None:
        def drop(there: dict) -> None:
            if there and there.get((role, namespace)):
                there[(role, namespace)] -= 1
                if not there[(role, namespace)]:
                    del there[(role, namespace)]

        for key in self.keys:
            if self.domain[key][node] is not None:
                drop(self.live[key].get(self.domain[key][node]))
                drop(self.total[key])

    def mark_wave_end(self) -> None:
        pass

    def checks(self) -> dict:
        return {"unaffine_pods": [self.unaffine, 0]}

    def control_nodes(self, names: list, role: str):
        """All of them: the control ignores the rule, and round robin then
        spreads the pods over every domain.  Where the nodes hold more than
        one domain of the key, the breach is sure; where they hold one, as
        upstream's SchedulingPodAffinity labels them, every node admits every
        pod once the first is bound, and no placement can break the rule."""
        return None
