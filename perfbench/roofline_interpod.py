"""The least work a cycle's solve could be where pods carry inter-pod terms,
from the deployment's shapes alone.

``roofline.solve_min_bytes(P, N, R)`` counts the plain solve: the tables read
once and one f32 pod x node matrix written once and read once.  A batch whose
pods, or whose cluster's bound pods, carry required (anti-)affinity terms reads
two more f32 tables once, ``node_matches`` and ``node_owners``, one row a
DISTINCT term and one column a node: 8TN bytes.  T is the terms the batch and
the bound owners really name (the ``n`` of the program's ``sched.encode.terms``
row), not the dim an implementation pads them to, so the count does not follow
the implementation.  The per-domain counts the solve keeps while it places pods
are derived from those tables and are working state, not input: no byte of them
is counted, so by this count too the solve is bound by memory bandwidth and a
solve that runs one wave a pod reads far under its roofline.
"""

from . import roofline


def solve_min_bytes(P: int, N: int, R: int, T: int) -> int:
    return roofline.solve_min_bytes(P, N, R) + 8 * T * N


def solve_min_seconds(P: int, N: int, R: int, T: int, hbm_bytes_per_s: float) -> float:
    return solve_min_bytes(P, N, R, T) / hbm_bytes_per_s
