#!/usr/bin/env python3
"""``configs/sched-policy-5000n.json``, written from two sources' constants.

    python3 benchmarks/tools/make_policy_config.py [--check]

The deployment is OpenShift Origin 3.x's default scheduler policy (the
``scheduler.json`` its admin guide documents: Region service affinity, Zone
service anti-affinity at weight 2) over the Kubernetes scalability load
test's services and pods, exactly as ``sched-load-5000n`` holds them
(``make_load_config.build()``): the same 3,936 services of 5, 30 and 250
pods in 50 namespaces, the same pods of 10m / 25 MiB. What differs is the
nodes' labels (two regions of three zones, the one assumed size) and the
scheduler's Policy, so ``load-5000n-backlog`` is this cell's exact control.
``--check`` says whether the committed file is what this writes. It imports
nothing of the program and nothing of the harness.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import make_load_config as load  # noqa: E402

OUT = os.path.join(os.path.dirname(HERE), "configs", "sched-policy-5000n.json")

# -- the policy's source (recalled, not fetched: ``assumed``) ---------------
POLICY = {
    "kind": "Policy", "apiVersion": "v1",
    "predicates": [
        {"name": "MatchNodeSelector"}, {"name": "PodFitsResources"},
        {"name": "PodFitsPorts"}, {"name": "NoDiskConflict"},
        {"name": "Region",
         "argument": {"serviceAffinity": {"labels": ["region"]}}}],
    "priorities": [
        {"name": "LeastRequestedPriority", "weight": 1},
        {"name": "ServiceSpreadingPriority", "weight": 1},
        {"name": "Zone", "weight": 2,
         "argument": {"serviceAntiAffinity": {"label": "zone"}}}]}
# -- the one assumed size: two regions of three zones ------------------------
REGIONS = ("r1", "r2")
ZONES = ("a", "b", "c")


def node_templates() -> list:
    """The load file's 5,000 nodes of 4 cpu / 32Gi dealt evenly over the
    six zones in name order, each node labelled with its region and zone."""
    pools = [(r, f"{r}-{z}") for r in REGIONS for z in ZONES]
    base, extra = divmod(load.NODES, len(pools))
    return [{"name": zone, "count": base + (k < extra),
             "capacity": {"cpu": "4", "memory": "32Gi"},
             "labels": {"region": region, "zone": zone}}
            for k, (region, zone) in enumerate(pools)]


def build() -> dict:
    doc = load.build()
    doc.update({
        "name": "sched-policy-5000n",
        "source": "OpenShift Origin 3.x admin guide, Scheduler: the default "
                  "scheduler.json policy; pods, services: https://github.com/"
                  "kubernetes/kubernetes/blob/release-1.9/test/e2e/"
                  "scalability/load.go",
        "deployment": "all-in-one control plane (apiserver, store, "
                      "reflectors, BatchScheduler) in one process on one TPU "
                      "v5e chip under OpenShift's default scheduler Policy; "
                      "zero kubelets; every pod a member of one service of "
                      "5, 30 or 250 pods, in one of 50 namespaces; nodes in "
                      "two regions of three zones",
        "node_templates": node_templates(),
        "reference": "serial_policy",
    })
    doc["scheduler"] = dict(doc["scheduler"], policy=POLICY)
    doc["env"] = dict(doc["env"], KTPU_PACK_TRANSFER="off")
    doc["guarantees"] = doc["guarantees"][:-1] + [
        "a pod's region is its service's first committed peer's region, "
        "where that pod's node selector names none (CheckServiceAffinity)",
        "a pod's Zone term counts its service's committed peers on the "
        "feasible nodes of each zone (ServiceAntiAffinity, weight 2)",
        "each decision is the serial rule's decision given every earlier "
        "decision (sequential commit) under the Policy: host and score, "
        "MatchNodeSelector, PodFitsResources, PodFitsPorts, NoDiskConflict, "
        "Region; LeastRequested 1 + ServiceSpreading 1 + Zone 2"]
    doc["assumed"] = dict(doc["assumed"], **{
        "policy": "the policy is OpenShift Origin 3.x's default "
                  "scheduler.json as its admin guide documents it, recalled, "
                  "not fetched (no network): predicates MatchNodeSelector, "
                  "PodFitsResources, PodFitsPorts, NoDiskConflict, Region "
                  "{serviceAffinity: {labels: [region]}}; priorities "
                  "LeastRequestedPriority 1, ServiceSpreadingPriority 1, Zone "
                  "{serviceAntiAffinity: {label: zone}} 2",
        "labels": "the source names the labels region and zone and no "
                  "layout: every node carries both, 2 regions of 3 zones "
                  "each, the 5,000 nodes dealt evenly in name order (834, "
                  "834, 833, 833, 833, 833); a zone's value names its region",
        "KTPU_PACK_TRANSFER": "pinned off: a program whose wave encoder "
                              "cannot hold ServiceAffinity takes the full "
                              "encoder, whose waves keep no resident planes "
                              "and, with the packed transfer on, compile an "
                              "unpack program a plane spec until the host "
                              "runs out of memory in set-up; with the pin it "
                              "completes. Where the encoder holds the "
                              "anchors the pin touches only the cold "
                              "placement (ship_inputs)"})
    return doc


def main(argv=None) -> int:
    text = load.render(build())
    if "--check" in (argv if argv is not None else sys.argv[1:]):
        with open(OUT) as f:
            same = f.read() == text
        print("same" if same else f"{OUT} differs from this tool's output")
        return 0 if same else 1
    with open(OUT, "w") as f:
        f.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
