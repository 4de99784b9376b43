"""Plain reference for a scheduler that runs under a Policy: the serial
scheduler's rule with the predicates and priorities a Policy file names
(``kube-scheduler --policy-config-file``), pod by pod, in plain numpy, with
nothing of the program in it.

The rule is upstream ``pkg/scheduler`` as ``serial_default.py`` cites it,
with the Policy's own sets in place of the provider's
(``factory.go`` CreateFromConfig; ``api/types.go`` for the file):

  for each pending pod, in the order the wave holds them:
    feasible  = nodes that pass every predicate the Policy lists, and only
                those: a predicate it does not list is not applied, not even
                PodFitsResources. Entries are kept by name, the last entry of
                a name standing (upstream keeps them in a map).
        PodFitsResources, PodFitsPorts, MatchNodeSelector  as serial_default
        CheckNodeLabelPresence(labels, presence)   every label present on
                           the node (presence) or every one absent
        CheckServiceAffinity(labels)               each label takes its value
                           from the pod's node selector where the selector
                           names it, else from the node of the first
                           committed peer of the pod's service, where that
                           node has the label; the node has to carry every
                           value so pinned
        NoDiskConflict, HostName, Schedulable      pass every node: no
                           configuration can state a volume, a pod's own
                           host or a cordoned node
    score(n)  = sum over the Policy's priorities of weight * priority(n);
                every entry applies, so repeated entries sum their weights
        LeastRequestedPriority, ServiceSpreadingPriority   as serial_default
        EqualPriority      1
        NodeLabelPriority(label, presence)         10 where the label's
                           presence on the node is as stated, else 0
        ServiceAntiAffinity(label)                 n = every committed peer
                           of the pod's service; count[v] = the peers on
                           FEASIBLE nodes whose label is v (upstream's
                           prioritizer is handed the filtered node list);
                           int(10 * ((n - count[v]) / n)) in float32 on a
                           node labelled v, 10 there with no peer, 0 on a
                           node without the label
                no priority at all: every node scores 1 (upstream returns
                EqualPriority's raw scores); every weight 0: no pod is
                placed (upstream's priority list comes out empty)
    best      = feasible nodes with the top score, in node-NAME order
    chosen    = best[fnv1a64(pod uid) % len(best)]
    commit: used, ports, peer counts and the service's first peer's node
            <- before the next pod looks

Without a Policy the sets are the default provider's
(``DEFAULT_PROVIDER``), and the answers are ``serial_default``'s, host and
score (``benchmarks/tests/test_serial_policy.py``). A pod's service and its
peers are as ``serial_default`` says.

Departures from upstream, each for a reason, beside serial_default's (a
third resource, a pod two services select and the tie-break):
  * the first peer: upstream's CheckServiceAffinity takes ``[0]`` of a
    store listing, whose order is map order, which is no order. Here it is
    the first peer committed.
  * a negative weight raises: the program refuses it too
    (``UnsupportedPolicy``), so no run could be held to it.
``solve_wave_uncommitted`` is the CONTROL: the commit put off to the end of
the wave.
"""

from __future__ import annotations

import numpy as np

from benchmarks.references import serial_default
from benchmarks.references.serial_resources import fnv1a64

# the default provider's sets (upstream defaults.go), as a Policy states them
DEFAULT_PROVIDER = {
    "predicates": [{"name": name} for name in (
        "PodFitsPorts", "PodFitsResources", "NoDiskConflict",
        "MatchNodeSelector", "HostName", "Schedulable")],
    "priorities": [{"name": "LeastRequestedPriority", "weight": 1},
                   {"name": "ServiceSpreadingPriority", "weight": 1},
                   {"name": "EqualPriority", "weight": 0}]}
_APPLIED = ("PodFitsResources", "PodFitsPorts", "MatchNodeSelector")
_VACUOUS = ("NoDiskConflict", "HostName", "Schedulable")
_NAMED_PRIORITIES = ("LeastRequestedPriority", "ServiceSpreadingPriority",
                     "EqualPriority")


def _predicates(policy: dict) -> list:
    """[(kind, argument)]: the Policy's predicates by name, the last entry
    of a name standing."""
    by_name: dict = {}
    for entry in policy.get("predicates", []):
        by_name[entry["name"]] = entry
    out = []
    for name, entry in by_name.items():
        argument = entry.get("argument") or {}
        if "serviceAffinity" in argument:
            out.append(("affinity", tuple(
                argument["serviceAffinity"].get("labels", []))))
        elif "labelsPresence" in argument:
            a = argument["labelsPresence"]
            out.append(("presence", (tuple(a.get("labels", [])),
                                     bool(a.get("presence", True)))))
        elif name in _APPLIED:
            out.append((name, None))
        elif name not in _VACUOUS:
            raise ValueError(f"this reference models no predicate {name!r}")
    return out


def _priorities(policy: dict) -> list:
    """[(kind, argument, weight)]: every entry of the Policy, in order."""
    out = []
    for entry in policy.get("priorities", []):
        name, weight = entry["name"], int(entry.get("weight", 1))
        if weight < 0:
            raise ValueError(f"priority {name!r} weight {weight}: this "
                             f"reference models no negative weight")
        argument = entry.get("argument") or {}
        if "serviceAntiAffinity" in argument:
            out.append(("anti_affinity",
                        argument["serviceAntiAffinity"].get("label", ""),
                        weight))
        elif "labelPreference" in argument:
            a = argument["labelPreference"]
            out.append(("preference", (a.get("label", ""),
                                       bool(a.get("presence", True))),
                        weight))
        elif name in _NAMED_PRIORITIES:
            out.append((name, None, weight))
        else:
            raise ValueError(f"this reference models no priority {name!r}")
    return out


class Cluster(serial_default.Cluster):
    """``serial_default``'s cluster, the Policy's sets, and the node of each
    service's first committed peer. ``policy``: the Policy object as the
    configuration states it; None for the default provider."""

    def __init__(self, nodes: dict, services=(), policy=None):
        super().__init__(nodes, services)
        policy = DEFAULT_PROVIDER if policy is None else policy
        self.predicates = _predicates(policy)
        self.priorities = _priorities(policy)
        self.none_placed = bool(self.priorities) and \
            not any(w for _, _, w in self.priorities)
        self.first = np.full(len(self.services), -1, dtype=np.int64)
        self._values: dict = {}

    def values(self, label: str) -> tuple:
        """([N] code of each node's value of the label, -1 where the node
        has no such label; {value: code})."""
        hit = self._values.get(label)
        if hit is None:
            vocab: dict = {}
            codes = np.array([vocab.setdefault(lbls[label], len(vocab))
                              if label in lbls else -1
                              for lbls in self.labels], dtype=np.int64)
            hit = self._values[label] = (codes, vocab)
        return hit

    def commit(self, host: str, pod: dict) -> None:
        super().commit(host, pod)
        service = pod["service"]
        if service is not None and self.first[service] < 0:
            self.first[service] = self.index[host]


def _service_affinity(cluster: Cluster, pod: dict, labels: tuple):
    selector = pod["template"].get("node_selector") or {}
    service = pod["service"]
    anchor = -1 if service is None else int(cluster.first[service])
    ok = np.ones(len(cluster.names), dtype=bool)
    for label in labels:
        codes, vocab = cluster.values(label)
        if label in selector:
            # a value that no node carries pins the pod to no node
            ok &= codes == vocab.get(selector[label], -2)
        elif anchor >= 0 and codes[anchor] >= 0:
            ok &= codes == codes[anchor]
    return ok


def _feasible(cluster: Cluster, pod: dict):
    cap, request = cluster.cap, pod["request"]
    feasible = np.ones(len(cluster.names), dtype=bool)
    for kind, argument in cluster.predicates:
        if kind == "PodFitsResources":
            if request.any():
                total = cluster.used + request
                feasible &= ((total <= cap) | (cap == 0)).all(axis=1)
        elif kind == "PodFitsPorts":
            for port in pod["ports"]:
                if port in cluster.ports:
                    feasible &= ~cluster.ports[port]
        elif kind == "MatchNodeSelector":
            feasible &= pod["selected"]
        elif kind == "presence":
            labels, presence = argument
            for label in labels:
                feasible &= (cluster.values(label)[0] >= 0) == presence
        else:
            feasible &= _service_affinity(cluster, pod, argument)
    return feasible


def _spread(n, count):
    """int(10 * ((n - count) / n)), every operation in float32."""
    share = (n - count).astype(np.float32) / np.float32(n)
    return (np.float32(10) * share).astype(np.int64)


def _least_requested(cluster: Cluster, pod: dict):
    cap = cluster.cap
    total = cluster.used + pod["request"]
    dim = np.where((cap == 0) | (total > cap), 0,
                   ((cap - total) * 10) // np.where(cap == 0, 1, cap))
    return dim.sum(axis=1) // 2


def _service_spreading(cluster: Cluster, pod: dict):
    n = len(cluster.names)
    if pod["service"] is None:
        return np.full(n, serial_default.SPREAD_NO_PEER, dtype=np.int64)
    count = cluster.peers[pod["service"]]
    most = int(count.max())
    if most == 0:
        return np.full(n, serial_default.SPREAD_NO_PEER, dtype=np.int64)
    return _spread(most, count)


def _anti_affinity(cluster: Cluster, pod: dict, label: str, feasible):
    codes, vocab = cluster.values(label)
    labelled = codes >= 0
    service = pod["service"]
    peers = 0 if service is None else int(cluster.peers[service].sum())
    if peers == 0 or not labelled.any():
        return np.where(labelled, 10, 0)
    counted = feasible & labelled
    count = np.bincount(codes[counted],
                        weights=cluster.peers[service][counted],
                        minlength=len(vocab)).astype(np.int64)
    return np.where(labelled, _spread(peers, count[codes.clip(0)]), 0)


def _score(cluster: Cluster, pod: dict, feasible):
    score = np.zeros(len(cluster.names), dtype=np.int64)
    if not cluster.priorities:
        return score + 1
    for kind, argument, weight in cluster.priorities:
        if weight == 0:
            continue
        if kind == "LeastRequestedPriority":
            part = _least_requested(cluster, pod)
        elif kind == "ServiceSpreadingPriority":
            part = _service_spreading(cluster, pod)
        elif kind == "EqualPriority":
            part = 1
        elif kind == "preference":
            label, presence = argument
            part = np.where((cluster.values(label)[0] >= 0) == presence,
                            10, 0)
        else:
            part = _anti_affinity(cluster, pod, argument, feasible)
        score += weight * part
    return score


def _decide(cluster: Cluster, uid: str, pod: dict):
    """One pod against the cluster as it stands: (host or None, score)."""
    if cluster.none_placed:
        return None, -1
    feasible = _feasible(cluster, pod)
    if not feasible.any():
        return None, -1
    score = _score(cluster, pod, feasible)
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
