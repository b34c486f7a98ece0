"""The deployments' hard rules, one file each.

A rule is what a configuration's templates bind the scheduler to beyond
"every pod is bound once": allocatable, a hard topology spread, a required
pod anti-affinity.  Each file here holds one rule whole: how to read it off
the templates, its counts, and the control that breaks it.  It imports
nothing of the program and nothing of another rule.  The reference's ledger is
the list of rules that apply to a deployment's templates; a later PR adds a
rule by adding a file, and nothing in this module knows any of their names.

What a rule file defines, in a class ``Rule``:

- ``control``: the name of the control run that breaks this rule and no other;
- ``held``: when the rule is read, ``"every_bind"`` (the comparison replays it
  in the store's own order of binds and deletions) or ``"whole_solves"`` (solve
  by solve, read at ``mark_wave_end``);
- ``claims(kind, constraint)`` (static): is this hard constraint of a template
  mine to check?  `kind` and `constraint` are as ``hard_constraints`` gives
  them;
- ``applies(templates)`` (static), and ``Rule(nodes, templates)``;
- ``admits(role, node, namespace)``: may such a pod be bound there now?  (The
  reference scheduler asks every rule; the replay asks none.)
- ``bind``, ``unbind`` (same arguments) and ``mark_wave_end()``: the replay;
- ``checks()``: ``{name: [value, limit]}``, the numbers ``correct`` holds;
- ``control_nodes(names, role)``: the nodes a control run that breaks this
  rule picks from, so that the breach is sure (None: all of them).
"""

from __future__ import annotations

import os

from ..manifest import ManifestError, load_function

RULES_DIR = os.path.dirname(os.path.abspath(__file__))

_REQUIRED = "requiredDuringScheduling"


def rule_classes() -> list:
    """Every rule file's ``Rule``, in the files' alphabetical order."""
    return [
        load_function(os.path.join(RULES_DIR, f), "Rule")
        for f in sorted(os.listdir(RULES_DIR))
        if f.endswith(".py") and not f.startswith("_")
    ]


def hard_constraints(template: dict) -> list:
    """Every hard scheduling constraint a pod template carries, as
    ``(kind, constraint)``: a ``DoNotSchedule`` spread constraint, each term
    of a ``requiredDuringScheduling*`` pod (anti-)affinity, a required node
    affinity, a nodeSelector.  Requests are no entry: allocatable binds every
    pod."""
    spec = template.get("spec") or {}
    out = []
    for c in spec.get("topologySpreadConstraints") or []:
        if c.get("whenUnsatisfiable", "DoNotSchedule") == "DoNotSchedule":
            out.append(("topologySpread", c))
    if spec.get("nodeSelector"):
        out.append(("nodeSelector", spec["nodeSelector"]))
    for kind, body in (spec.get("affinity") or {}).items():
        for key, value in (body or {}).items():
            if not key.startswith(_REQUIRED) or not value:
                continue
            if kind == "nodeAffinity":
                out.append((kind, value))
            else:
                out.extend((kind, term) for term in value)
    return out


def describe(kind: str, constraint: dict) -> str:
    key = constraint.get("topologyKey") if isinstance(constraint, dict) else None
    return f"{kind} on {key}" if key else kind


def require_claimed(templates: dict) -> None:
    """A deployment cannot be listed with a rule nobody checks: a hard
    constraint that no rule file answers for stops the run, by name."""
    classes = rule_classes()
    for role, template in templates.items():
        for kind, constraint in hard_constraints(template):
            if not any(cls.claims(kind, constraint) for cls in classes):
                raise ManifestError(
                    f"the {role} pod template carries a hard constraint that no file under "
                    f"perfbench/rules/ checks: {describe(kind, constraint)} ({constraint}); "
                    "`correct` could not see it broken, so the deployment does not run"
                )


def applicable(nodes: list, templates: dict) -> list:
    """The rules of one deployment, built on its nodes and templates.  (The
    guard against a constraint nobody checks is ``require_claimed``, which the
    harness calls once, before any load.)"""
    return [cls(nodes, templates) for cls in rule_classes() if cls.applies(templates)]
