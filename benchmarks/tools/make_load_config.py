#!/usr/bin/env python3
"""``configs/sched-load-5000n.json``, written from the source's constants.

    python3 benchmarks/tools/make_load_config.py [--check]

The deployment is the Kubernetes scalability load test (``source`` below):
ReplicationControllers of 5, 30 and 250 pods, half / a quarter / a quarter
of ``30 x nodes`` pods, one Service a group, groups dealt round-robin to one
namespace a hundred nodes. The file holds one pod template and one service
a group, four thousand of each, so a reviewer reads this file and not that
one; ``--check`` says whether the committed file is what this writes.
It imports nothing of the program and nothing of the harness.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(os.path.dirname(HERE), "configs", "sched-load-5000n.json")

# -- the source's constants (recalled, not fetched: ``assumed``) ------------
NODES = 5000
PODS_PER_NODE = 30
GROUP_SIZES = {"big": 250, "medium": 30, "small": 5}    # creation order
GROUP_SHARES = {"big": 4, "medium": 4, "small": 2}      # total / (share*size)
NODES_PER_NAMESPACE = 100
CPU_REQUEST_MILLI = 10
MEMORY_REQUEST_BYTES = 26214400
# -- the cut: how many of the source's groups the configuration holds -------
HELD_PERCENT = 24


def groups(percent: int = 100) -> list:
    """[(class, index, size)] in the source's order of creation: every big
    group, every medium one, every small one."""
    total = NODES * PODS_PER_NODE
    out = []
    for cls, size in GROUP_SIZES.items():
        count = total // (GROUP_SHARES[cls] * size)
        out += [(cls, i, size) for i in range(count * percent // 100)]
    return out


def build() -> dict:
    namespaces = [f"load-{i:02d}" for i in range(NODES // NODES_PER_NAMESPACE)]
    held, published = groups(HELD_PERCENT), groups()
    templates, services = [], []
    for cls, i, size in held:
        name = f"load-{cls}-{i:05d}"
        namespace = namespaces[i % len(namespaces)]
        templates.append({
            "name": name, "weight": size, "namespace": namespace,
            "limits": {"cpu": f"{CPU_REQUEST_MILLI}m",
                       "memory": str(MEMORY_REQUEST_BYTES)},
            "labels": {"name": name}, "in": ["warm", "window"]})
        services.append({"name": name, "namespace": namespace,
                         "selector": {"name": name}})

    def counts(gs):
        return {cls: sum(1 for g in gs if g[0] == cls) for cls in GROUP_SIZES}

    return {
        "name": "sched-load-5000n",
        "source": "https://github.com/kubernetes/kubernetes/blob/release-1.9/"
                  "test/e2e/scalability/load.go#Load capacity: 30 pods per "
                  "node, ReplicationController, services; later perf-tests "
                  "clusterloader2 testing/load",
        "deployment": "all-in-one control plane (apiserver, store, "
                      "reflectors, BatchScheduler) in one process on one TPU "
                      "v5e chip; zero kubelets; every pod a member of one "
                      "service of 5, 30 or 250 pods, in one of 50 namespaces",
        "nodes": NODES,
        "measured_pods": "as many as the traffic creates in --seconds",
        "init_pods": "the traffic file's warm_rounds (1+2+...+1024 = 2047), "
                     "of the same templates",
        "groups": f"the deployment's first {HELD_PERCENT} % of each class: "
                  f"{counts(held)} of {counts(published)}",
        "source_sizes": {
            "nodes": NODES, "pods_per_node": PODS_PER_NODE,
            "pods": NODES * PODS_PER_NODE,
            "group_sizes": GROUP_SIZES,
            "groups": counts(published), "services": len(published),
            "nodes_per_namespace": NODES_PER_NAMESPACE,
            "namespaces": len(namespaces),
            "cpu_request_milli": CPU_REQUEST_MILLI,
            "memory_request_bytes": MEMORY_REQUEST_BYTES},
        "held_sizes": {
            "pods": sum(size for _, _, size in held),
            "groups": counts(held), "services": len(held),
            "namespaces": len(namespaces)},
        "namespace": namespaces[0],
        "node_templates": [{
            "name": "node-default", "file": "node-default.yaml",
            "count": NODES, "capacity": {"cpu": "4", "memory": "32Gi"}}],
        "pod_templates": templates,
        "services": services,
        "scheduler": {
            "algorithm": "tpu-batch", "provider": "DefaultProvider",
            "wave_size": 1024, "wave_linger_s": 0.02, "pipeline": False,
            "packed_transfer": "auto",
            "prewarm": "fill-trigger, as shipped"},
        "env": {"KTPU_WAVE_ROUTER": "device"},
        "reference": "serial_default",
        "kernel_program": "pallas",
        "guarantees": [
            "a pod the client saw bound is bound to that node in a final "
            "LIST",
            "each pod is bound exactly once",
            "no node holds more than its capacity",
            "a pod's ServiceSpreading term counts every committed peer of "
            "its service in its namespace",
            "each decision is the serial rule's decision given every earlier "
            "decision (sequential commit): host and score, PodFitsResources, "
            "PodFitsPorts, MatchNodeSelector; LeastRequested + "
            "ServiceSpreading"],
        "assumed": {
            "recalled": "every number under source_sizes is recalled from "
                        "upstream test/e2e/scalability/load.go (v1.3-v1.15; "
                        "test/e2e/load.go before) and its successor "
                        "perf-tests clusterloader2/testing/load/config.yaml, "
                        "not fetched (no network): totalPods = 30 x nodes; "
                        "smallGroupSize 5, mediumGroupSize 30, bigGroupSize "
                        "250; smallGroupCount = totalPods / (2 x 5), medium "
                        "= totalPods / (4 x 30), big = totalPods / (4 x "
                        "250); nodeCountPerNamespace 100; CpuRequest 10 "
                        "(10m), MemRequest 26214400 (25 MiB); one Service a "
                        "group with selector name: <group>, the label every "
                        "pod of the group carries",
            "node_shape": "the source states no node shape (it runs on the "
                          "cluster it is pointed at): the accepted node "
                          "template, 4 cpu / 32Gi, as in sched-basic-5000n",
            "limits_as_requests": "the source's pod states its 10m / 25 MiB "
                                  "under resources.requests; in this API "
                                  "version the predicates read "
                                  "resources.limits",
            "groups_as_templates": "a ReplicationController's pod template "
                                   "is a pod template of weight = the "
                                   "group's size; no controller runs, the "
                                   "feeder posts the pods",
            "plan_not_batches": "the source creates its groups in batches "
                                "of 30 big, 5 medium and 1 small a step and "
                                "scales each to its size; the harness's plan "
                                "shuffles one block of all 36,000 pods from "
                                "the seed, so a group's pods arrive spread "
                                "over the window; the warm-up's 2,047 pods "
                                "are drawn from a block of their own, so a "
                                "group may end a few pods over its size",
            "services_first": "every service exists before the scheduler "
                              "starts; the source creates a group's service "
                              "just before its controller",
            "services_cut": "16,400 services cut to 3,936 (groups): what a "
                            "51 s window can fill, and a set-up that posts "
                            "them in seconds",
            "names": "load-<class>-<index>; the source's names carry a "
                     "random suffix",
            "container": "the harness's defaults (pause); the source's "
                         "image is its own",
            "headroom": "400 pods a node by cpu (4 cpu / 10m), 1,310 by "
                        "memory: 2,000,000 on 5,000 nodes; the plan repeats "
                        "only past 36,000 pods, 660 pods/s over the 51 s "
                        "window and the warm-up",
            "KTPU_WAVE_ROUTER": "pinned to device, as in the other files",
            "seed": "varies what the source leaves free: pod names and "
                    "uids, the order in which nodes register, the order of "
                    "the plan"},
        "reduced": ["measured_pods", "init_pods", "groups"],
    }


def render(doc: dict) -> str:
    """One line a template and a service: four thousand of each."""
    rows = {"pod_templates": doc["pod_templates"],
            "services": doc["services"]}
    head = json.dumps({k: ("@" + k if k in rows else v)
                       for k, v in doc.items()}, indent=1)
    for key, items in rows.items():
        body = ",\n  ".join(json.dumps(i) for i in items)
        head = head.replace(f'"@{key}"', "[\n  " + body + "\n ]")
    return head + "\n"


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
