"""The least work a cycle's solve could be, from its shapes alone.

Kept with the benchmark and blind to what implements the solve: with P the pod
bucket, N the node bucket and R the resource columns of the snapshot, the
tables are read once (f32: 4(NR + PR) bytes) and one f32 pod x node matrix is
written once and read once (8PN bytes).  No arithmetic worth counting rides on
those bytes, so by this count the solve is bound by memory bandwidth.
"""


def solve_min_bytes(P: int, N: int, R: int) -> int:
    return 4 * (N * R + P * R) + 8 * P * N


def solve_min_seconds(P: int, N: int, R: int, hbm_bytes_per_s: float) -> float:
    return solve_min_bytes(P, N, R) / hbm_bytes_per_s
