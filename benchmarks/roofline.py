"""What one wave's solve needs, from its sizes alone, whatever implements it.

``solve_work(dims)`` -> (ops, bytes) for a wave of ``P`` pods against ``N``
nodes with ``R`` resource dimensions (the sizes as shipped, padding and all:
the device works on what it is given).

bytes — every input plane read once and every output written once, at the
widths the wave ships them (``models/batch_solver.SolverInputs``):
  node planes   N x R x 4 each: cap, fit_used, score_used   (int32)
                N x R x 1: advertises (bool); N x 1: fit_exceeded,
                node_extra_ok (bool); N x 4: score_static (int32);
                N x 4 each: one word of port and of disk bitmask (uint32)
  pod rows      P x R x 4: req; P x 4 each: port word, disk word,
                host index, tie_hi, tie_lo, group id (int32/uint32)
  static mask   P x N x 1: may pod p go on node n at all (selector, host,
                cordon). It is derived on the device from the planes above,
                but every pod step has to read its row once, and one byte a
                cell is the least any implementation streams (the kernel of
                PR 22 streams it as int32, four bytes a cell; counting one
                keeps the share a lower bound).
  outputs       P x 4 x 2: chosen node and its score (int32)

ops — P x N x OPS_PER_CELL integer operations, counted from the serial rule
(``benchmarks/references/serial_resources.py``, which is upstream's
PodFitsResources + LeastRequested + spreading + tie-break) for one pod on
one node:
  fit          per dimension: used + req (1), <= cap (1)            2R
               and over dimensions and with the static mask          R
  score        per dimension: cap - total (1), * 10 (1), // cap (1)  3R
               sum over dimensions (R - 1), // R (1), + the spread
               score and the weighted sum (2)                        R + 2
  choose       mask the infeasible (1), compare with the running
               maximum (1), equal-to-top (1), rank among the best (1),
               pick the k-th (1)                                     5
  commit       add the request on the chosen node: O(R) a pod, not a cell
  => OPS_PER_CELL = 7R + 7  (21 at R = 2)
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def ops_per_cell(R: int) -> int:
    return 7 * R + 7


def solve_work(dims: dict) -> tuple:
    P, N, R = int(dims["P"]), int(dims["N"]), int(dims.get("R", 2))
    node_bytes = N * (3 * R * 4 + R + 2 + 4 + 2 * 4)
    pod_bytes = P * (R * 4 + 6 * 4)
    mask_bytes = P * N
    out_bytes = P * 4 * 2
    return (P * N * ops_per_cell(R),
            node_bytes + pod_bytes + mask_bytes + out_bytes)


def peaks_for(device_kind: str) -> dict:
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"benchmarks/peaks.json (has {sorted(table)})")
    return table[device_kind]


def least_seconds(dims: dict, peaks: dict) -> tuple:
    """(seconds, which bound) — the larger of ops over the peak rate and
    bytes over the peak bandwidth."""
    ops, nbytes = solve_work(dims)
    t_ops = ops / peaks["int_ops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "ops") if t_ops > t_bytes else (t_bytes, "bytes")
