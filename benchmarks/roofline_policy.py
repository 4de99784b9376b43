"""What one wave's solve needs under a Policy with ServiceAffinity and
ServiceAntiAffinity: ``roofline_groups.py``'s count (resources, ports,
LeastRequested, the group rows and the ServiceSpreading term) and the two
policy terms beside it.

``solve_work(dims)`` -> (ops, bytes) for a wave of ``P`` pods against ``N``
nodes with ``R`` resource dimensions whose pods name ``G`` distinct service
groups (``G`` may be a mean over waves, so a fraction), under ``L``
ServiceAffinity labels and ``A`` anti-affinity labels of ``V`` values each.

bytes, beside ``roofline_groups.solve_work``'s:
  node_aff_vals  N x L x 4: each node's value code of each affinity label,
                 read once                                         (int32)
  zone_idx       A x N x 4: each node's zone code, read once       (int32)
  zone_counts0   A x G x V x 4: each group's peers by zone, read once
  anchors        G x L x 4: each group row's anchor values, read once
  a pod step     L x 4 read: its group's anchor, as earlier commits of
                 the wave left it; A x V x 4 read: its group's counts by
                 zone, as they stand; A x 4 written: one more peer in the
                 chosen node's zone

ops, beside ``roofline_groups``'s, for one pod on one node
(``benchmarks/references/serial_policy.py``):
  ServiceAffinity, a label: the pinned code or the anchor's (1), compare
  with the node's value (1), AND into the feasible mask (1)           3L
  ServiceAntiAffinity, a label: gather the node's zone count (1),
  n - count (1), the quotient (1), times ten (1), the truncation (1),
  times the weight (1), the add into the score (1)                    7A
"""

from __future__ import annotations

from benchmarks import roofline_groups

AFFINITY_OPS_PER_LABEL = 3
ANTI_AFFINITY_OPS_PER_LABEL = 7


def solve_work(dims: dict) -> tuple:
    P, N = int(dims["P"]), int(dims["N"])
    G = float(dims["G"])
    L, A, V = int(dims["L"]), int(dims["A"]), int(dims["V"])
    ops, nbytes = roofline_groups.solve_work(dims)
    planes = N * L * 4 + A * N * 4 + A * G * V * 4 + G * L * 4
    steps = P * (L * 4 + A * V * 4 + A * 4)
    cell = AFFINITY_OPS_PER_LABEL * L + ANTI_AFFINITY_OPS_PER_LABEL * A
    return ops + P * N * cell, nbytes + planes + steps


def least_seconds(dims: dict, peaks: dict) -> tuple:
    """(seconds, which bound), as ``roofline.least_seconds``."""
    ops, nbytes = solve_work(dims)
    t_ops = ops / peaks["int_ops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "ops") if t_ops > t_bytes else (t_bytes, "bytes")
