#!/usr/bin/env python3
"""``configs/sched-topology-5000n.json``, written from two sources' constants.

    python3 benchmarks/tools/make_topology_config.py [--check]

The deployment is the sample policy of OpenShift Origin 3.x's admin guide
that defines three topological levels: region and zone (service affinity,
one predicate over both labels) and rack (service anti-affinity, weight 1),
over the Kubernetes scalability load test's services and pods exactly as
``sched-policy-5000n`` holds them (``make_policy_config.build()``): the same
3,936 services of 5, 30 and 250 pods in 50 namespaces, the same pods of
10m / 25 MiB, the same scheduler and env, and the same two regions of three
zones. What differs is a rack label on every node (each zone's nodes dealt
over racks of at most 40, the one assumed size) and the Policy, so
``policy-5000n-backlog`` is this cell's exact control. ``--check`` says
whether the committed file is what this writes. It imports nothing of the
program and nothing of the harness.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import make_load_config as load  # noqa: E402
import make_policy_config as policy  # noqa: E402

OUT = os.path.join(os.path.dirname(HERE), "configs",
                   "sched-topology-5000n.json")

# -- the policy's source (recalled, not fetched: ``assumed``) ---------------
# the sample, as the guide prints it (its "..." lines stand for the other
# entries, read as the default scheduler.json's, which sched-policy-5000n
# holds):
SAMPLE = """kind: "Policy"
version: "v1"
predicates:
...
  - name: "RegionZoneAffinity"
    argument:
      serviceAffinity:
        labels:
          - "region"
          - "zone"
priorities:
...
  - name: "RackSpread"
    weight: 1
    argument:
      serviceAntiAffinity:
        label: "rack"
"""
POLICY = {
    "kind": "Policy", "apiVersion": "v1",
    "predicates": policy.POLICY["predicates"][:4] + [
        {"name": "RegionZoneAffinity",
         "argument": {"serviceAffinity": {"labels": ["region", "zone"]}}}],
    "priorities": policy.POLICY["priorities"][:2] + [
        {"name": "RackSpread", "weight": 1,
         "argument": {"serviceAntiAffinity": {"label": "rack"}}}]}
# -- the one assumed size: a 42U rack of 1U servers, less its top-of-rack
# switches, holds at most 40 nodes
RACK_NODES = 40


def node_templates() -> list:
    """The control's six zones (``make_policy_config.node_templates``),
    each zone's nodes dealt evenly in name order over the fewest racks of at
    most ``RACK_NODES``: 21 racks a zone, 126 in all, of 40 and 39 nodes.
    A rack's value names its zone, so no two zones share one."""
    out = []
    for zone in policy.node_templates():
        racks = -(-zone["count"] // RACK_NODES)
        base, extra = divmod(zone["count"], racks)
        for k in range(racks):
            rack = f"{zone['name']}-k{k + 1:02d}"
            out.append({"name": rack, "count": base + (k < extra),
                        "capacity": dict(zone["capacity"]),
                        "labels": dict(zone["labels"], rack=rack)})
    return out


def build() -> dict:
    doc = policy.build()
    doc.update({
        "name": "sched-topology-5000n",
        "source": "OpenShift Origin 3.x admin guide, Scheduler, Sample "
                  "Policy Configurations: region, zone (affinity) -> rack "
                  "(anti-affinity); pods, services: kubernetes release-1.9 "
                  "test/e2e/scalability/load.go",
        "deployment": "all-in-one control plane (apiserver, store, "
                      "reflectors, BatchScheduler) in one process on one TPU "
                      "v5e chip under OpenShift's three-level topology "
                      "Policy; zero kubelets; every pod a member of one "
                      "service of 5, 30 or 250 pods, in one of 50 "
                      "namespaces; nodes in 2 regions, 6 zones and 126 "
                      "racks",
        "node_templates": node_templates(),
    })
    doc["scheduler"] = dict(doc["scheduler"], policy=POLICY)
    doc["guarantees"] = doc["guarantees"][:-3] + [
        "a pod's region and zone are its service's first committed peer's "
        "region and zone, where that pod's node selector names none "
        "(CheckServiceAffinity)",
        "a pod's RackSpread term counts its service's committed peers on "
        "the feasible nodes of each rack (ServiceAntiAffinity, weight 1)",
        "each decision is the serial rule's decision given every earlier "
        "decision (sequential commit) under the Policy: host and score, "
        "MatchNodeSelector, PodFitsResources, PodFitsPorts, NoDiskConflict, "
        "RegionZoneAffinity; LeastRequested 1 + ServiceSpreading 1 + "
        "RackSpread 1"]
    assumed = {k: v for k, v in doc["assumed"].items() if k != "labels"}
    assumed.update({
        "policy": "the policy is the sample of OpenShift Origin 3.x's admin "
                  "guide (Scheduler, Sample Policy Configurations) that "
                  "defines three topological levels, region (affinity) -> "
                  "zone (affinity) -> rack (anti-affinity), recalled, not "
                  "fetched (no network), as the guide prints it: "
                  + SAMPLE.strip().replace("\n", " / ") + ". The sample "
                  "writes '...' for its other entries: read as the default "
                  "scheduler.json that sched-policy-5000n holds, predicates "
                  "MatchNodeSelector, PodFitsResources, PodFitsPorts, "
                  "NoDiskConflict; priorities LeastRequestedPriority 1, "
                  "ServiceSpreadingPriority 1",
        "labels": "the source names the three labels and no layout: the "
                  "control's 2 regions of 3 zones (834, 834, 833, 833, 833, "
                  "833 nodes), every node also labelled with its rack, each "
                  "zone's nodes dealt evenly in name order over the fewest "
                  "racks of at most 40 nodes (a 42U rack of 1U servers less "
                  "its top-of-rack switches): 21 racks a zone, 126 in all, "
                  "86 of 40 nodes and 40 of 39. A rack's value names its "
                  "zone (r1-a-k01), so no two zones share one: vocabularies "
                  "region 2, zone 6, rack 126"})
    doc["assumed"] = assumed
    return doc


def render(doc: dict) -> str:
    """``make_load_config.render``, with one line a node template too."""
    text = load.render(dict(doc, node_templates="@node_templates"))
    body = ",\n  ".join(json.dumps(t) for t in doc["node_templates"])
    return text.replace('"@node_templates"', "[\n  " + body + "\n ]")


def main(argv=None) -> int:
    text = render(build())
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
