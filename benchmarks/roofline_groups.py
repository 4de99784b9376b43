"""What one wave's solve needs when its pods have services: ``roofline.py``'s
count, which knows no group plane, and the ServiceSpreading term beside it.

``solve_work(dims)`` -> (ops, bytes) for a wave of ``P`` pods against ``N``
nodes with ``R`` resource dimensions whose pods name ``G`` distinct service
groups (``G`` may be a mean over waves, so a fraction).

bytes, beside ``roofline.solve_work``'s:
  group rows    G x (N + 1) x 4: each group's peers by node and off the
                list, read once                                   (int32)
  membership    P x 4: one word of group membership a pod; a pod of this
                deployment has one service
  a pod step    N x 4 read: the counts of the pod's own group over the
                nodes, as they stand after every earlier commit of the
                wave — the term has to see them afresh each step; and 4
                written: the chosen node's count, one more peer
                (an implementation that re-reads or re-writes every row
                a step does more than it must, and its share says so)

ops, beside ``roofline.ops_per_cell``'s, for one pod on one node
(``benchmarks/references/serial_default.py``: int(10 * ((max - count) /
max)) in float32):
  the running maximum of the counts (1), max - count (1), the quotient
  (1), times ten (1), the truncation (1), the add into the score (1)   6
"""

from __future__ import annotations

from benchmarks import roofline

SPREAD_OPS_PER_CELL = 6


def solve_work(dims: dict) -> tuple:
    P, N = int(dims["P"]), int(dims["N"])
    G = float(dims["G"])
    ops, nbytes = roofline.solve_work(dims)
    rows = G * (N + 1) * 4
    steps = P * (4 + N * 4 + 4)
    return ops + P * N * SPREAD_OPS_PER_CELL, nbytes + rows + steps


def least_seconds(dims: dict, peaks: dict) -> tuple:
    """(seconds, which bound), as ``roofline.least_seconds``."""
    ops, nbytes = solve_work(dims)
    t_ops = ops / peaks["int_ops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "ops") if t_ops > t_bytes else (t_bytes, "bytes")
