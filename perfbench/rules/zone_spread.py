"""A hard topology spread over zones: after every whole solve, in every
namespace, the zone counts of the pods the selector counts differ by at most
maxSkew.

As in Kubernetes, a topologySpreadConstraint's selector counts matching pods
of the incoming pod's own namespace only: the rule is held namespace by
namespace.  It is read after every whole solve (``mark_wave_end``), because
the binds of one solve reach the client shard by shard and in no order.  The
templates here carry at most one such constraint.
"""

from __future__ import annotations

ZONE_KEY = "topology.kubernetes.io/zone"


def spread_rule(template: dict):
    """(topology key, maxSkew, selector labels) of the template's hard spread
    constraint, or None."""
    for c in (template.get("spec") or {}).get("topologySpreadConstraints") or []:
        if c.get("whenUnsatisfiable", "DoNotSchedule") == "DoNotSchedule":
            sel = ((c.get("labelSelector") or {}).get("matchLabels")) or {}
            return c["topologyKey"], int(c.get("maxSkew", 1)), dict(sel)
    return None


def _matches(template: dict, selector: dict) -> bool:
    labels = (template.get("metadata") or {}).get("labels") or {}
    return all(labels.get(k) == v for k, v in selector.items())


class Rule:
    control = "skew"
    held = "whole_solves"

    @staticmethod
    def claims(kind: str, constraint) -> bool:
        return kind == "topologySpread" and constraint.get("topologyKey") == ZONE_KEY \
            and set(constraint.get("labelSelector") or {}) <= {"matchLabels"}

    @staticmethod
    def applies(templates: dict) -> bool:
        return any(spread_rule(t) is not None for t in templates.values())

    def __init__(self, nodes: list, templates: dict):
        self.zone = {
            n["metadata"]["name"]: (n["metadata"].get("labels") or {}).get(ZONE_KEY)
            for n in nodes
        }
        self.rule = None
        for t in templates.values():
            self.rule = self.rule or spread_rule(t)
        # which roles' pods the spread selector counts
        self.counted = {role: _matches(t, self.rule[2]) for role, t in templates.items()}
        self.zones = sorted({z for z in self.zone.values() if z is not None})
        self.zone_count: dict = {}      # namespace -> {zone: counted pods}
        self.max_skew_seen = 0

    def counts(self, namespace: str) -> dict:
        """The counted pods of one namespace, zone by zone (every zone of the
        cluster is a domain, an empty one too)."""
        c = self.zone_count.get(namespace)
        if c is None:
            c = self.zone_count[namespace] = dict.fromkeys(self.zones, 0)
        return c

    def admits(self, role: str, node: str, namespace: str) -> bool:
        if not self.counted[role]:
            return True
        c = self.counts(namespace)
        return c[self.zone[node]] + 1 - min(c.values()) <= self.rule[1]

    def bind(self, role: str, node: str, namespace: str) -> None:
        if self.counted[role] and self.zone[node] is not None:
            self.counts(namespace)[self.zone[node]] += 1

    def unbind(self, role: str, node: str, namespace: str) -> None:
        if self.counted[role] and self.zone[node] is not None:
            self.counts(namespace)[self.zone[node]] -= 1

    def mark_wave_end(self) -> None:
        for c in self.zone_count.values():
            self.max_skew_seen = max(self.max_skew_seen, max(c.values()) - min(c.values()))

    def checks(self) -> dict:
        return {"max_zone_skew": [self.max_skew_seen, self.rule[1]]}

    def control_nodes(self, names: list, role: str):
        """The control puts every counted pod into the first node's zone."""
        if not self.counted[role]:
            return None
        return [n for n in names if self.zone[n] == self.zone[names[0]]]
