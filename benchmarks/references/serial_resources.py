"""Plain reference for resource-only clusters: the serial scheduler's rule
for the default provider, pod by pod, with nothing of the program in it.

It covers what the ``sched-basic-*`` configurations state and refuses the
rest by raising: nodes with a cpu and a memory capacity (both above zero)
and no third resource, pods that request cpu and memory, no host ports, no
services, no node selectors (``serial_default.py`` models those).

The interface every reference has (``harness/correct.py`` calls no other):
``Cluster(nodes, services)`` with ``nodes`` {name: node template} and
``services`` [service] as ``harness/deployment.py`` reads them from the
configuration; ``solve_wave(cluster, pods)`` and ``solve_wave_uncommitted``
with ``pods`` [(uid, pod template)] in wave order.

The rule (upstream ``pkg/scheduler``: ``generic_scheduler.go`` Schedule,
``predicates.go`` PodFitsResources, ``priorities.go`` LeastRequested,
``spreading.go`` with no service, EqualPriority):

  for each pending pod, in the order the wave holds them:
    feasible  = nodes where used + request <= capacity, for cpu and memory
    score(n)  = (cpu_score + mem_score) // 2 + 10
                dim_score = ((cap - used - request) * 10) // cap
                (10: ServiceSpreading with no service; the default
                provider registers EqualPriority with weight 0)
    best      = feasible nodes with the top score, in node-NAME order
    chosen    = best[fnv1a64(pod uid) % len(best)]
    commit: used[chosen] += request          <- before the next pod looks

``solve_wave`` is that rule. ``solve_wave_uncommitted`` is the CONTROL:
the same rule with the commit put off to the end of the wave, so that
every pod of a wave decides against the state before the wave. It breaks
the guarantee "a decision sees every earlier decision" and is the short
cut that would tempt a later PR (all pods of a wave in parallel).
"""

from __future__ import annotations

import numpy as np

FNV64_OFFSET = 0xCBF29CE484222325
FNV64_PRIME = 0x100000001B3
SPREAD_NO_SERVICE = 10

_SUFFIX = {"Ki": 2 ** 10, "Mi": 2 ** 20, "Gi": 2 ** 30, "Ti": 2 ** 40,
           "k": 10 ** 3, "M": 10 ** 6, "G": 10 ** 9, "T": 10 ** 12}


def fnv1a64(text: str) -> int:
    h = FNV64_OFFSET
    for b in text.encode("utf-8"):
        h ^= b
        h = (h * FNV64_PRIME) & 0xFFFFFFFFFFFFFFFF
    return h


def milli(quantity: str) -> int:
    """'4' -> 4000, '100m' -> 100 (cpu is compared in milli-units)."""
    q = str(quantity).strip()
    if q.endswith("m"):
        return int(q[:-1])
    return whole(q) * 1000


def whole(quantity: str) -> int:
    """'32Gi' -> bytes, '500Mi' -> bytes, '7' -> 7."""
    q = str(quantity).strip()
    for suffix, mult in _SUFFIX.items():
        if q.endswith(suffix):
            return int(q[:-len(suffix)]) * mult
    return int(q)


def cpu_memory(resources, what: str) -> tuple:
    """(cpu_milli, memory_bytes) of a template's capacity or limits. A pair
    is taken as it is: ``tests/test_mesh_arm.py`` (tier-1, not this
    benchmark's to edit) still hands pairs over."""
    if isinstance(resources, tuple):
        return resources
    if set(resources) - {"cpu", "memory"}:
        raise ValueError(f"this reference models cpu and memory only; "
                         f"{what} states {sorted(resources)}")
    return milli(resources.get("cpu", 0)), whole(resources.get("memory", 0))


def _capacity(node) -> tuple:
    return cpu_memory(node if isinstance(node, tuple) else node["capacity"],
                 "a node")


def _request(pod) -> tuple:
    if isinstance(pod, tuple):
        return pod
    if pod.get("node_selector") or pod.get("host_ports"):
        raise ValueError(f"this reference models no node selector and no "
                         f"host port; pod template {pod.get('name')!r} "
                         f"states one")
    return cpu_memory(pod["limits"], "a pod")


class Cluster:
    """Node capacities and what has been committed onto them so far.
    ``nodes``: {name: node template}; ``services``: none."""

    def __init__(self, nodes: dict, services=()):
        if not nodes:
            raise ValueError("a cluster needs nodes")
        if services:
            raise ValueError("this reference models no service")
        self.names = sorted(nodes)                   # node-list order
        self.index = {n: i for i, n in enumerate(self.names)}
        self.cap = np.array([_capacity(nodes[n]) for n in self.names],
                            dtype=np.int64)
        if (self.cap <= 0).any():
            raise ValueError("this reference models only nodes with a cpu "
                             "and a memory capacity above zero")
        self.used = np.zeros_like(self.cap)

    def commit(self, host: str, request) -> None:
        self.used[self.index[host]] += np.asarray(request, dtype=np.int64)


def _decide(cluster: Cluster, uid: str, request):
    """One pod against the cluster as it stands: (host or None, score)."""
    req = np.asarray(request, dtype=np.int64)
    if not req.any():
        raise ValueError("this reference models only pods that request "
                         "cpu or memory")
    total = cluster.used + req                       # [N, 2]
    feasible = (total <= cluster.cap).all(axis=1)
    if not feasible.any():
        return None, -1
    dim = ((cluster.cap - total) * 10) // cluster.cap
    score = dim.sum(axis=1) // 2 + SPREAD_NO_SERVICE
    top = int(score[feasible].max())
    best = np.flatnonzero(feasible & (score == top))
    return cluster.names[int(best[fnv1a64(uid) % len(best)])], top


def solve_wave(cluster: Cluster, pods: list) -> list:
    """``pods``: [(uid, pod template)] in wave order. Returns
    [(host or None, score)], and leaves the decisions committed."""
    out = []
    for uid, pod in pods:
        request = _request(pod)
        host, score = _decide(cluster, uid, request)
        if host is not None:
            cluster.commit(host, request)
        out.append((host, score))
    return out


def solve_wave_uncommitted(cluster: Cluster, pods: list) -> list:
    """The control: every pod of the wave decides against the state before
    the wave; the commits follow together."""
    out = [_decide(cluster, uid, _request(pod)) for uid, pod in pods]
    for (uid, pod), (host, _score) in zip(pods, out):
        if host is not None:
            cluster.commit(host, _request(pod))
    return out
