"""Full-scale equivalence gate: one complete benchmark config solved by
the device batch path and by the serial oracle, with every decision
compared. The serial oracle costs tens of minutes of pure Python at full
shape, so this runs out-of-band (once per config per round) rather than
inside bench.py; results are recorded in
FULLGATE_r{N}[_{config}].json for the judge. bench.py's per-run gates
cover budget-sized slices of the same node axis.

Configs mirror bench.py's matrix exactly (same builders, same policies):
north_star (default), affinity, binpack3, gang. The reference discipline
being reproduced is the full-suite-at-full-shape oracle run
(ref: test/e2e/density.go:173-215).

Usage: python hack/fullgate.py [--config C] [--pods P] [--nodes N]
                               [--out FILE]
"""

from __future__ import annotations

import argparse
import datetime
import json
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="north_star",
                    choices=["north_star", "affinity", "binpack3", "gang"])
    ap.add_argument("--pods", type=int, default=0,
                    help="override pod count (default: the config's shape)")
    ap.add_argument("--nodes", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    sys.path.insert(0, ".")
    import jax

    import bench
    from kubernetes_tpu.models.batch_solver import decisions_to_names, solve
    from kubernetes_tpu.models.oracle import solve_serial
    from kubernetes_tpu.models.policy import batch_policy_from
    from kubernetes_tpu.models.snapshot import encode_snapshot

    # the ONE definition of shapes/policies, shared with the bench matrix
    n_nodes, n_pods, build_kw = bench.FULL_SHAPES[args.config]
    policy = bench.affinity_policy() if args.config == "affinity" else None
    n_nodes = args.nodes or n_nodes
    n_pods = args.pods or n_pods

    backend = jax.default_backend()
    total_pods = n_pods or (build_kw.get("gang_groups", 0)
                            * build_kw.get("gang_size", 8))
    print(f"[fullgate] {args.config}: building {total_pods} pods x "
          f"{n_nodes} nodes (backend={backend})", file=sys.stderr,
          flush=True)
    nodes, existing, pending, services = bench.build_cluster(
        n_nodes, n_pods, **build_kw)

    batch_policy = batch_policy_from(policy=policy) if policy else None
    t0 = time.perf_counter()
    snap = encode_snapshot(nodes, existing, pending, services,
                           policy=batch_policy)
    chosen, _ = solve(snap)
    batch = decisions_to_names(snap, chosen)
    batch_s = time.perf_counter() - t0
    print(f"[fullgate] batch path done in {batch_s:.2f}s; running the "
          f"serial oracle (slow)", file=sys.stderr, flush=True)

    t0 = time.perf_counter()
    serial = solve_serial(nodes, existing, pending, services, policy=policy,
                          gangs=True)
    serial_s = time.perf_counter() - t0

    divergent = sum(1 for a, b in zip(batch, serial) if a != b)
    record = {
        "config": f"{args.config} {len(pending)} pods x {n_nodes} nodes "
                  f"(full scale)",
        "equivalent": divergent == 0,
        "divergent_decisions": divergent,
        "scheduled": sum(1 for h in batch if h is not None),
        "batch_total_s": round(batch_s, 2),
        "serial_oracle_s": round(serial_s, 1),
        "serial_oracle_pods_per_s": round(len(pending) / serial_s, 1),
        "platform": backend,
        "date": datetime.date.today().isoformat(),
    }
    if build_kw.get("gang_groups"):
        # full-scale all-or-nothing invariant, same as bench.py's check
        import numpy as np
        rid = np.asarray(snap.pod_rid)[: len(pending)]
        ok = np.asarray(chosen)[: len(pending)] >= 0
        partial = [int(g) for g in np.unique(rid[rid >= 0])
                   if ok[rid == g].any() != ok[rid == g].all()]
        record["gang_groups_partial"] = len(partial)
        record["equivalent"] = record["equivalent"] and not partial
    out = json.dumps(record, indent=1)
    print(out)
    if args.out:
        with open(args.out, "w") as f:
            f.write(out + "\n")
    return 0 if record["equivalent"] else 1


if __name__ == "__main__":
    sys.exit(main())
