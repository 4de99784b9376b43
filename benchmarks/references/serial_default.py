"""Plain reference for the default provider whole: the serial scheduler's
rule with node selectors, host ports and services, pod by pod, in plain
numpy, with nothing of the program in it.

The rule is upstream ``pkg/scheduler`` as ``serial_resources.py`` cites it
(``generic_scheduler.go`` Schedule, ``predicates.go``, ``priorities.go``,
``spreading.go``), the default provider's sets:

  for each pending pod, in the order the wave holds them:
    feasible  = nodes that pass every predicate
        PodFitsResources   used + request <= capacity, for cpu and memory;
                           a pod that requests nothing fits anywhere; a
                           capacity of zero does not constrain
        PodFitsPorts       none of the pod's host ports (0 is no port) is
                           taken by a pod committed on the node
        MatchNodeSelector  every key = value of the pod's node selector is
                           among the node's labels
    score(n)  = LeastRequested + ServiceSpreading      (weights 1 and 1;
                the provider registers EqualPriority with weight 0)
        LeastRequested     (cpu_score + mem_score) // 2; calculateScore =
                           ((cap - used - request) * 10) // cap, and 0 where
                           cap is 0 or used + request > cap
        ServiceSpreading   10 with no service or no peer committed; else
                           int(10 * ((max - count[n]) / max)) in float32,
                           count[n] the peers on n, max the most on any node
    best      = feasible nodes with the top score, in node-NAME order
    chosen    = best[fnv1a64(pod uid) % len(best)]
    commit: used, ports and peer counts of the chosen node   <- before the
            next pod looks

A pod's service is the one of its namespace whose selector (not empty) is
among the pod's labels; its peers are the committed pods of that namespace
that the same selector matches, warm-up pods included.

Departures from upstream, each for a reason:
  * a third resource: upstream's two functions name cpu and memory and
    nothing else, so there is no rule of its to hold a program to (the
    program's own generalisation divides by the resources that the
    FEASIBLE nodes state, pod by pod). A node or a pod that states a third
    resource raises; such a deployment brings a reference that says which
    rule it means.
  * a pod that two services select: upstream takes "the first" of a list
    whose order is its store's map order, which is no order. This reference
    raises instead of guessing.
  * the tie-break: upstream draws ``rand.Int() % len(best)``; a reference
    has to be replayable, so it is the FNV-1a hash of the pod's uid, as in
    ``serial_resources.py``.
Not modelled, because no configuration can state them yet: NoDiskConflict
(volumes), HostName (a pod created with its host), cordoned nodes.

With no selector, port or service its answers are ``serial_resources``'
exactly (``benchmarks/tests/test_serial_default.py``).
``solve_wave_uncommitted`` is the same CONTROL: the commit put off to the
end of the wave.
"""

from __future__ import annotations

import numpy as np

from benchmarks.references.serial_resources import cpu_memory, fnv1a64

SPREAD_NO_PEER = 10


class Cluster:
    """What the nodes state and what has been committed onto them so far.
    ``nodes``: {name: node template}; ``services``: [service]."""

    def __init__(self, nodes: dict, services=()):
        if not nodes:
            raise ValueError("a cluster needs nodes")
        self.names = sorted(nodes)                   # node-list order
        self.index = {n: i for i, n in enumerate(self.names)}
        rows: dict = {}           # id(template) -> (cpu_milli, memory_bytes)
        for t in nodes.values():
            if id(t) not in rows:
                rows[id(t)] = cpu_memory(t["capacity"], "a node")
        self.cap = np.array([rows[id(nodes[n])] for n in self.names],
                            dtype=np.int64)          # [N, 2]
        self.used = np.zeros_like(self.cap)
        self.labels = [nodes[n].get("labels", {}) for n in self.names]
        self.services = list(services)
        self.peers = [np.zeros(len(self.names), dtype=np.int64)
                      for _ in self.services]        # by service, by node
        self.ports: dict = {}                        # host port -> taken[N]
        self._pods: dict = {}                        # id(template) -> parsed

    def pod(self, template: dict) -> dict:
        """What the rule needs of a pod template, worked out once."""
        hit = self._pods.get(id(template))
        if hit is not None and hit["template"] is template:
            return hit
        selector = template.get("node_selector") or {}
        labels = template.get("labels") or {}
        mine = [i for i, s in enumerate(self.services)
                if s["namespace"] == template["namespace"] and s["selector"]
                and all(labels.get(k) == v
                        for k, v in s["selector"].items())]
        if len(mine) > 1:
            raise ValueError(f"pod template {template.get('name')!r} is "
                             f"selected by {len(mine)} services; upstream "
                             f"takes the first of an unordered list")
        parsed = self._pods[id(template)] = {
            "template": template,
            "request": np.array(cpu_memory(template["limits"], "a pod"),
                                dtype=np.int64),
            "selected": np.array([all(node.get(k) == v
                                      for k, v in selector.items())
                                  for node in self.labels]),
            "ports": [p for p in template.get("host_ports") or [] if p],
            "service": mine[0] if mine else None}
        return parsed

    def commit(self, host: str, pod: dict) -> None:
        at = self.index[host]
        self.used[at] += pod["request"]
        for port in pod["ports"]:
            self.ports.setdefault(
                port, np.zeros(len(self.names), dtype=bool))[at] = True
        if pod["service"] is not None:
            self.peers[pod["service"]][at] += 1


def _decide(cluster: Cluster, uid: str, pod: dict):
    """One pod against the cluster as it stands: (host or None, score)."""
    cap, request = cluster.cap, pod["request"]
    total = cluster.used + request                   # [N, 2]
    feasible = pod["selected"].copy()
    if request.any():
        feasible &= ((total <= cap) | (cap == 0)).all(axis=1)
    for port in pod["ports"]:
        if port in cluster.ports:
            feasible &= ~cluster.ports[port]
    if not feasible.any():
        return None, -1
    dim = np.where((cap == 0) | (total > cap), 0,
                   ((cap - total) * 10) // np.where(cap == 0, 1, cap))
    score = dim.sum(axis=1) // 2
    spread = SPREAD_NO_PEER
    if pod["service"] is not None:
        count = cluster.peers[pod["service"]]
        most = int(count.max())
        if most > 0:
            share = (most - count).astype(np.float32) / np.float32(most)
            spread = (np.float32(10) * share).astype(np.int64)
    score = score + spread
    top = int(score[feasible].max())
    best = np.flatnonzero(feasible & (score == top))
    return cluster.names[int(best[fnv1a64(uid) % len(best)])], top


def solve_wave(cluster: Cluster, pods: list) -> list:
    """``pods``: [(uid, pod template)] in wave order. Returns
    [(host or None, score)], and leaves the decisions committed."""
    out = []
    for uid, template in pods:
        pod = cluster.pod(template)
        host, score = _decide(cluster, uid, pod)
        if host is not None:
            cluster.commit(host, pod)
        out.append((host, score))
    return out


def solve_wave_uncommitted(cluster: Cluster, pods: list) -> list:
    """The control: every pod of the wave decides against the state before
    the wave; the commits follow together."""
    out = [_decide(cluster, uid, cluster.pod(template))
           for uid, template in pods]
    for (_uid, template), (host, _score) in zip(pods, out):
        if host is not None:
            cluster.commit(host, cluster.pod(template))
    return out
