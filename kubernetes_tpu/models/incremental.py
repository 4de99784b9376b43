"""IncrementalEncoder — delta-maintained snapshot encoding for churn.

The full encoder (models/snapshot.encode_snapshot) re-derives every plane
from the object graph each wave — the analog of the reference rebuilding
``MapPodsToMachines`` per scheduling cycle (ref: pkg/scheduler/
predicates.go:354-375). At 10k nodes that costs ~10^2 ms per wave, which
SURVEY §7 hard part (c) says must not be paid under 1k pods/s churn.

This encoder keeps the node-side planes *resident* and applies deltas:

- **sticky vocabularies**: host ports, (key,value) node-selector pairs, PD
  names, namespaces, and resource dimensions intern into append-only
  vocabularies whose axes are pow-2 bucketed — so a churning cluster
  re-uses at most log2 distinct compiled solver shapes instead of
  recompiling per wave;
- **refcounted node planes**: per-node port/PD use increments on pod
  arrival and decrements on departure, so the per-wave cost is O(changed
  pods), not O(cluster);
- **service groups kept where they are few**: a pod's services come from an
  index over selector pairs (``_ServiceIndex``), and a group's peers are
  kept sparsely (group -> node -> count), maintained pod by pod. A wave
  carries one dense ``group_counts`` row for each distinct group its
  pending pods name, made from the sparse counts in O(its peers); no row
  outlives its wave, so no service and no new group ends a residency
  epoch (docs/design/solver.md section 2a);
- **order-exact overflow handling**: greedy-fit usage equals the plain sum
  on every node whose total fits (the common case); only genuinely
  overflowing nodes trigger the sequential in-order walk, over the current
  list order — keeping bit-identity with the full encoder and the serial
  oracle;
- **pod-axis bucketing**: the pending wave pads to a pow-2 length with
  null rows (pinned to an impossible host, zero requests) that can never
  place or perturb real decisions, so variable wave sizes share compiled
  programs.

The caller keeps the same lister-shaped interface as the full encoder —
``encode(nodes, existing, pending, services)`` — and the encoder diffs
against its cached state by object identity + uid, so it slots into the
BatchScheduler without plumbing watch events through the scheduler.

Not supported: policies with CheckServiceAffinity labels (anchor state is
first-peer-in-list-order dependent, so removal would need order-replay);
construction raises ValueError and the scheduler falls back to the full
encoder. Pod specs are treated as immutable after creation (they are, in
the reference's API: only status/host change post-bind).

Decision equivalence (not byte equivalence — vocab order and padding
differ) against encode_snapshot is fuzz-tested under churn in
tests/test_incremental.py.
"""

from __future__ import annotations

import functools
import itertools
import operator
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from kubernetes_tpu.api import types as api
from kubernetes_tpu.models import gang
from kubernetes_tpu.models.policy import BatchPolicy, DEFAULT_BATCH_POLICY
from kubernetes_tpu.models.snapshot import (
    ClusterSnapshot,
    _fnv1a64_batch,
    _pow2_pad,
    greedy_fit_accumulators,
)
from kubernetes_tpu.scheduler import predicates as _preds
from kubernetes_tpu.scheduler.generic import pod_tie_break_key
from kubernetes_tpu.util import metrics, tracing

__all__ = ["IncrementalEncoder"]

# KTPU_DEBUG=1: re-derive the resident evictable planes from the cached
# pod records every emitted wave and assert equality with the O(bands)
# incrementally-maintained ones (models/preempt.derive_evict_planes is
# the authoritative from-scratch twin)
_DEBUG_VERIFY_EVICT = os.environ.get("KTPU_DEBUG", "") not in ("", "0")

# Residency epochs (models/resident.py): one counter for the process, so no
# two encoders, and no encoder before and after a restore(), ever hand out
# the same epoch.
_EPOCHS = itertools.count(1)
# the touched-row log keeps at most this many entries; a consumer that asks
# for rows it no longer holds is told so (None) and re-places its planes
_TOUCH_LOG_MAX = 1 << 16


# A wave's group axis: the floor of every pow-2 vocabulary while no wave has
# named more groups than that; from the first that does, a function of the
# pod bucket (``IncrementalEncoder._group_bucket``), so that the group axis
# adds no program shapes of its own.
GROUP_FLOOR = 8


def wave_groups() -> metrics.Counter:
    return metrics.default_registry().counter(
        "scheduler_wave_groups_total",
        "Distinct service groups named by the pending pods of the waves the "
        "encoder built: the group rows those waves carried (beside "
        "scheduler_wave_solve_seconds_count)")


def peered_pods() -> metrics.Counter:
    return metrics.default_registry().counter(
        "scheduler_wave_peered_pods_total",
        "Pending pods whose service group had a committed peer when their "
        "wave was built: the pods whose ServiceSpreading term could tell "
        "nodes apart (beside scheduler_wave_pods_total)")


def constrained_pods() -> metrics.Counter:
    return metrics.default_registry().counter(
        "scheduler_wave_constrained_pods_total",
        "Pending pods the encoder built into waves that carry a node "
        "selector, a host port or the membership of a service (beside "
        "scheduler_wave_pods_total)")


class _PodRec:
    """Cached contribution of one existing pod to the resident planes."""

    __slots__ = ("host_idx", "req", "ports", "pds", "ns_code", "svcs",
                 "prio", "name", "ns", "labels")

    def __init__(self, host_idx: int, req: List[Tuple[int, int]],
                 ports: List[int], pds: List[int], ns_code: int,
                 svcs: Tuple[int, ...], prio: int = 0, name: str = "",
                 ns: str = "", labels: Optional[dict] = None):
        self.host_idx = host_idx   # node row, or N-sentinel for off-list
        self.req = req             # [(resource column, amount)]
        self.ports = ports         # port vocab columns (with multiplicity)
        self.pds = pds             # pd vocab columns
        self.ns_code = ns_code
        self.svcs = svcs           # services that select it, ascending
        self.prio = prio           # resolved pod priority (kube-preempt)
        self.name = name           # pod name (victim materialization)
        self.ns = ns               # pod namespace
        self.labels = labels       # the pod's own (re-matched when the
        #                            service set changes)


class _Vocab:
    """Append-only interner with pow-2 bucketed capacity."""

    def __init__(self):
        self.index: Dict = {}

    def intern(self, key) -> int:
        i = self.index.get(key)
        if i is None:
            i = self.index[key] = len(self.index)
        return i

    def __len__(self):
        return len(self.index)

    @property
    def cap(self) -> int:
        return _pow2_pad(len(self.index))


class _ServiceIndex:
    """Which services select a pod, found from the pod's label pairs: a pair
    leads to the services whose selector holds it (in the pod's namespace,
    or in none), and a service selects the pod when as many of the pod's
    pairs led to it as its selector has. O(the pod's labels x the services
    that share a pair), not O(services). Built once a service set; never
    written afterwards, so checkpoints share it."""

    __slots__ = ("by_pair", "size")

    def __init__(self, services: Sequence[api.Service]):
        self.by_pair: Dict[Tuple[str, str, str], List[int]] = {}
        self.size: List[int] = []
        for si, s in enumerate(services):
            selector = s.spec.selector or {}
            self.size.append(len(selector))
            ns = s.metadata.namespace or ""
            for k, v in selector.items():
                self.by_pair.setdefault((ns, k, v), []).append(si)

    def match(self, namespace: str, labels: Optional[dict]
              ) -> Tuple[int, ...]:
        """The services, by index and ascending, whose selector (not empty)
        is among ``labels`` and whose namespace is the pod's (a service
        without one selects in every namespace)."""
        if not labels or not self.by_pair:
            return ()
        hits: Dict[int, int] = {}
        by_pair = self.by_pair
        for k, v in labels.items():
            for ns in (namespace, "") if namespace else ("",):
                for si in by_pair.get((ns, k, v), ()):
                    hits[si] = hits.get(si, 0) + 1
        if not hits:
            return ()
        size = self.size
        return tuple(sorted(si for si, n in hits.items() if n == size[si]))


class IncrementalEncoder:
    def __init__(self, policy: Optional[BatchPolicy] = None):
        self.policy = policy or DEFAULT_BATCH_POLICY
        if self.policy.affinity_labels:
            raise ValueError(
                "IncrementalEncoder does not support CheckServiceAffinity "
                "policies (anchor state is arrival-order dependent); use "
                "encode_snapshot")
        self._nodes_key: Optional[List[Tuple]] = None
        self._svc_key: Optional[List[Tuple]] = None
        self._services: List[api.Service] = []
        self._index = _ServiceIndex(())
        self._pods: Dict[str, _PodRec] = {}
        # service groups, kept where they are few: (namespace code, service
        # index) -> {node row (N: off-list): peers there}, and the same
        # peers by zone ([A, V]) under a zone anti-affinity policy
        self._peers: Dict[Tuple[int, int], Dict[int, int]] = {}
        self._zone_peers: Dict[Tuple[int, int], np.ndarray] = {}
        # the group axis of a wave (_group_bucket): the most groups one
        # wave has named, and whether that ever passed the floor
        self._g_seen = 0
        self._g_wide = False
        self._ports = _Vocab()
        self._sels = _Vocab()
        self._pds = _Vocab()
        self._ns = _Vocab()
        # kube-preempt: sticky priority-band vocabulary (value -> slot) +
        # the monotone minimum over every value ever interned; bands emit
        # (self._preempt_emitted, sticky for shape stability) once any
        # pending pod sits strictly above the floor
        self._bands = _Vocab()
        self._band_min: Optional[int] = None
        self._preempt_emitted = False
        self._resource_names: List[str] = []
        # resident planes (allocated by _rebuild_nodes)
        self._N = 0
        # O(changed) accounting, consumed by the tier-1 complexity guards
        # (tests/test_incremental.py): zone_writes counts single-element
        # zone-count updates, group_writes the peer-count ones;
        # evict_writes the per-band evictable-plane updates;
        # node_rebuilds the full resident-plane rebuilds
        self.op_counts: Dict[str, int] = {
            "zone_writes": 0, "group_writes": 0, "node_rebuilds": 0,
            "evict_writes": 0}
        # residency (models/resident.py): the node rows _add_pod and
        # _remove_pod wrote, in order, under a sequence number; the epoch
        # changes whenever the rows alone no longer say what changed
        self._touch_log: List[int] = []
        self._touch_base = 0
        self._new_epoch("first")

    # -- residency: what changed since a snapshot -----------------------------
    def _new_epoch(self, why: str) -> None:
        """A consumer that keeps the node planes of an earlier snapshot may
        patch them with the touched rows only inside one epoch; everything
        else a wave can do to them ends it (``why`` says what)."""
        self._epoch = next(_EPOCHS)
        self._epoch_why = why
        self._touch_base += len(self._touch_log)
        self._touch_log.clear()

    def _touch(self, i: int) -> None:
        log = self._touch_log
        log.append(i)
        if len(log) > _TOUCH_LOG_MAX:
            half = len(log) // 2
            del log[:half]
            self._touch_base += half

    def _touched(self, since: int, *, epoch: int, upto: int
                 ) -> Optional[List[int]]:
        """Node rows written in [since, upto) of ``epoch``, duplicates and
        all; None when the log cannot say (another epoch by now, rows
        trimmed away, or a snapshot older than the one last applied)."""
        lo, hi = since - self._touch_base, upto - self._touch_base
        if epoch != self._epoch or lo < 0 or hi < lo:
            return None
        return self._touch_log[lo:hi]

    # -- node side ----------------------------------------------------------
    @staticmethod
    def _node_fp(n: api.Node) -> Tuple:
        return (n.metadata.name,
                bool(n.spec.unschedulable),
                tuple(sorted((n.metadata.labels or {}).items())),
                tuple(sorted((k, str(v.value)) for k, v in
                             (n.spec.capacity or {}).items())))

    def _nodes_changed(self, nodes: Sequence[api.Node]) -> bool:
        if self._nodes_key is None or len(nodes) != self._N:
            return True
        key = self._nodes_key
        for i, n in enumerate(nodes):
            cached_obj, cached_fp = key[i]
            if n is cached_obj:
                continue  # same object the store handed out before
                # (the cache holds the reference, so CPython can't reuse
                # the address for a different node behind our back)
            if self._node_fp(n) != cached_fp:
                return True
            key[i] = (n, cached_fp)  # relisted but identical
        return False

    def _rebuild_nodes(self, nodes: Sequence[api.Node],
                       existing: Sequence[api.Pod],
                       services: Sequence[api.Service],
                       why: str = "nodes") -> None:
        """Node set/order/labels/capacity changed: rebuild every resident
        plane (node order defines the tie-break axis, so there is no safe
        partial update on reorder). Sticky vocabularies survive."""
        self._nodes_key = [(n, self._node_fp(n)) for n in nodes]
        self._N = N = len(nodes)
        self._node_names = [n.metadata.name for n in nodes]
        self._node_index = {nm: i for i, nm in enumerate(self._node_names)}
        self._node_labels = [dict(n.metadata.labels or {}) for n in nodes]

        scored = _preds.resource_universe(nodes)
        # sticky universe: scored dims first, previously-seen request-only
        # dims keep their columns (append-only indices)
        old = self._resource_names
        extras = [r for r in old if r not in scored]
        self._resource_names = scored + extras
        self._rix = {name: r for r, name in enumerate(self._resource_names)}
        R = len(self._resource_names)
        self._cap = np.zeros((N, R), np.int64)
        self._advertised = np.zeros((N, R), bool)
        for i, n in enumerate(nodes):
            for name, q in (n.spec.capacity or {}).items():
                r = self._rix.get(name)
                if r is not None:
                    self._cap[i, r] = _preds.resource_value(name, q)
                    self._advertised[i, r] = True

        self._score_used = np.zeros((N, R), np.int64)
        self._port_cnt = np.zeros((N, self._ports.cap), np.int32)
        self._pd_cnt = np.zeros((N, self._pds.cap), np.int32)
        self._node_sel = np.zeros((N, self._sels.cap), bool)
        for (k, v), col in self._sels.index.items():
            for i, lbls in enumerate(self._node_labels):
                if lbls.get(k) == v:
                    self._node_sel[i, col] = True

        # policy planes (all node-derived); cordon folds in first,
        # unconditionally (spec.unschedulable is in the fingerprint, so
        # a cordon/uncordon triggers the rebuild that lands here)
        self._extra_ok = np.ones(N, bool)
        for i, n in enumerate(nodes):
            if n.spec.unschedulable:
                self._extra_ok[i] = False
        for i, lbls in enumerate(self._node_labels):
            for labels, presence in self.policy.label_presence:
                if any((l in lbls) != presence for l in labels):
                    self._extra_ok[i] = False
                    break
        self._score_static = np.zeros(N, np.int32)
        for i, lbls in enumerate(self._node_labels):
            self._score_static[i] = sum(
                10 * w for label, presence, w in self.policy.label_prefs
                if (label in lbls) == presence)
        A = len(self.policy.anti_affinity)
        self._node_zone = np.full((A, N), -1, np.int32)
        for a, (label, _w) in enumerate(self.policy.anti_affinity):
            vocab: Dict[str, int] = {}
            for i, lbls in enumerate(self._node_labels):
                v = lbls.get(label)
                if v is not None:
                    if v not in vocab:
                        vocab[v] = len(vocab)
                    self._node_zone[a, i] = vocab[v]
        # zone codes are node-label-derived, so V is fixed until the next
        # node-plane rebuild; same V rule as snapshot_to_host_inputs
        self._zone_V = max(1, int(self._node_zone.max(initial=-1)) + 1)

        # peer counts are by node row and by zone code: start over and
        # re-apply the cached pods
        self._peers = {}
        self._zone_peers = {}
        # kube-preempt resident planes: [N, B, R] evictable capacity +
        # [N, B] counts over the sticky band vocabulary, plus the
        # per-node pod registry victim materialization reads
        Bc = self._bands.cap if len(self._bands) else 0
        self._evict_cap = np.zeros((N, Bc, R), np.int64)
        self._evict_cnt = np.zeros((N, Bc), np.int32)
        self._node_pods: Dict[int, Dict[str, _PodRec]] = {}
        self.op_counts["node_rebuilds"] += 1
        self._pods.clear()
        self._set_services(services)
        for p in existing:
            self._add_pod(p)
        self._new_epoch(why)

    # -- services -----------------------------------------------------------
    @staticmethod
    def _svc_fp(s: api.Service) -> Tuple:
        return (s.metadata.namespace, s.metadata.name,
                tuple(sorted((s.spec.selector or {}).items())))

    def _set_services(self, services: Sequence[api.Service]) -> None:
        self._svc_key = [self._svc_fp(s) for s in services]
        self._services = list(services)
        self._index = _ServiceIndex(services)

    def _services_changed(self, services: Sequence[api.Service]) -> bool:
        """Whether the service set is another than the one indexed. The
        store hands out the same objects until an event replaces one, so
        the common wave compares identities and fingerprints nothing."""
        known = self._services
        if self._svc_key is not None and len(services) == len(known) and \
                all(map(operator.is_, services, known)):
            return False
        if self._svc_key is None or len(services) != len(self._svc_key) \
                or any(self._svc_fp(s) != k
                       for s, k in zip(services, self._svc_key)):
            return True
        self._services = list(services)   # relisted, and the same
        return False

    def _sync_services(self, services: Sequence[api.Service]) -> None:
        """A service was added, dropped or changed: index the new set and
        match every cached pod again. No node plane holds a service's
        peers, so no residency epoch ends; the records are replaced, not
        written, because checkpoints share them."""
        if not self._services_changed(services):
            return
        self._set_services(services)
        self._peers = {}
        self._zone_peers = {}
        for uid, old in self._pods.items():
            rec = _PodRec(old.host_idx, old.req, old.ports, old.pds,
                          old.ns_code, self._index.match(old.ns, old.labels),
                          prio=old.prio, name=old.name, ns=old.ns,
                          labels=old.labels)
            self._pods[uid] = rec
            on_node = self._node_pods.get(old.host_idx)
            if on_node is not None and uid in on_node:
                on_node[uid] = rec
            self._count_peer(rec, 1)

    def _count_peer(self, rec: _PodRec, d: int) -> None:
        """One pod arrives at (d = 1) or leaves (d = -1) the sparse peer
        counts of every group that selects it — a pod counts toward EVERY
        matching group, exactly as the full encoder's member_exist matrix
        does (an existing peer is a peer of any service that selects it,
        not just its own first). A node's count that falls to zero goes,
        and so does a group without peers."""
        i = rec.host_idx
        for si in rec.svcs:
            key = (rec.ns_code, si)
            at = self._peers.get(key)
            if at is None:
                at = self._peers[key] = {}
            n = at.get(i, 0) + d
            if n:
                at[i] = n
            else:
                del at[i]
                if not at:
                    del self._peers[key]
            self.op_counts["group_writes"] += 1
            self._zone_delta(key, i, d)

    def _zone_delta(self, key: Tuple[int, int], host_idx: int,
                    d: int) -> None:
        """Mirror one peer-count update into the group's zone counts: the
        pod on ``host_idx`` adds/removes one peer in that node's zone for
        every anti-affinity dim. Off-list (host_idx == N) and unlabeled
        nodes belong to no zone — exactly the nodes the former per-wave
        one-hot contraction zeroed out."""
        A = self._node_zone.shape[0]
        if host_idx >= self._N or not A:
            return
        for a in range(A):
            zv = int(self._node_zone[a, host_idx])
            if zv >= 0:
                zones = self._zone_peers.get(key)
                if zones is None:
                    zones = self._zone_peers[key] = np.zeros(
                        (A, self._zone_V), np.int32)
                zones[a, zv] += d
                self.op_counts["zone_writes"] += 1

    def _group_bucket(self, n_groups: int, Ppad: int) -> int:
        """The group axis of a wave that names ``n_groups`` distinct groups
        among ``Ppad`` (padded) pods. The floor while no wave has named
        more than the floor; from the first that does, the pod bucket held
        between the floor and the kernel's cap (so the group axis is a
        function of the pod axis and adds no program shapes, and the
        prewarm has nothing to chase); a wave that passes the cap — the
        wave loop cuts before it (``group_cut``) — takes the pod bucket
        whole and leaves the kernel for the scan."""
        self._g_seen = max(self._g_seen, n_groups)
        if n_groups > GROUP_FLOOR:
            self._g_wide = True
        if not self._g_wide:
            return GROUP_FLOOR
        G = max(GROUP_FLOOR, min(Ppad, self.group_cap()))
        return G if n_groups <= G else _pow2_pad(n_groups)

    def group_cap(self, n_nodes: Optional[int] = None) -> int:
        """The most group rows the Pallas kernel takes at this cluster's
        size beside the encoder's other vocabularies."""
        from kubernetes_tpu.ops import pallas_solver
        return pallas_solver.max_groups(
            self._N if n_nodes is None else n_nodes,
            max(2, len(self._resource_names)),
            self._ports.cap // 32 or 1, self._pds.cap // 32 or 1)

    def group_cut(self, pending: Sequence[api.Pod],
                  services: Sequence[api.Service], n_nodes: int) -> int:
        """How many of ``pending``, from the front, one wave may hold: the
        longest prefix whose pods name no more distinct groups than the
        kernel takes rows. All of them wherever there is no service."""
        if not services or \
                len(pending) <= (cap := self.group_cap(n_nodes)):
            return len(pending)
        self._sync_services(list(services))
        match = self._index.match
        seen: set = set()
        for j, p in enumerate(pending):
            svcs = match(p.metadata.namespace, p.metadata.labels)
            if svcs:
                seen.add((p.metadata.namespace, svcs[0]))
                if len(seen) > cap:
                    return j
        return len(pending)

    # -- pod deltas ---------------------------------------------------------
    def _grow_cols(self, arr: np.ndarray, cap: int, fill=0) -> np.ndarray:
        if arr.shape[1] >= cap:
            return arr
        grown = np.full((arr.shape[0], cap), fill, arr.dtype)
        grown[:, :arr.shape[1]] = arr
        return grown

    def _resource_col(self, name: str) -> int:
        r = self._rix.get(name)
        if r is None:
            r = self._rix[name] = len(self._resource_names)
            self._new_epoch("column")
            self._resource_names.append(name)
            self._cap = np.pad(self._cap, ((0, 0), (0, 1)))
            self._advertised = np.pad(self._advertised, ((0, 0), (0, 1)))
            self._score_used = np.pad(self._score_used, ((0, 0), (0, 1)))
            self._evict_cap = np.pad(self._evict_cap,
                                     ((0, 0), (0, 0), (0, 1)))
        return r

    def _band_col(self, prio: int) -> int:
        """Sticky band slot for a priority value, growing the resident
        evictable planes' band axis on first sight."""
        b = self._bands.intern(prio)
        if self._band_min is None or prio < self._band_min:
            self._band_min = prio
        cap = self._bands.cap
        if self._evict_cnt.shape[1] < cap:
            self._new_epoch("column")
            self._evict_cap = np.pad(
                self._evict_cap,
                ((0, 0), (0, cap - self._evict_cap.shape[1]), (0, 0)))
            self._evict_cnt = self._grow_cols(self._evict_cnt, cap)
        return b

    def _port_col(self, port: int) -> int:
        col = self._ports.intern(port)
        if self._port_cnt.shape[1] < self._ports.cap:
            self._new_epoch("column")
        self._port_cnt = self._grow_cols(self._port_cnt, self._ports.cap)
        return col

    def _pd_col(self, pd: str) -> int:
        col = self._pds.intern(pd)
        if self._pd_cnt.shape[1] < self._pds.cap:
            self._new_epoch("column")
        self._pd_cnt = self._grow_cols(self._pd_cnt, self._pds.cap)
        return col

    def _sel_col(self, kv: Tuple[str, str]) -> int:
        known = kv in self._sels.index
        col = self._sels.intern(kv)
        self._node_sel = self._grow_cols(self._node_sel, self._sels.cap,
                                         fill=False)
        if not known:  # backfill the new column from resident node labels
            self._new_epoch("column")
            k, v = kv
            for i, lbls in enumerate(self._node_labels):
                if lbls.get(k) == v:
                    self._node_sel[i, col] = True
        return col

    def _add_pod(self, pod: api.Pod) -> None:
        uid = pod.metadata.uid
        host = pod.status.host
        i = self._node_index.get(host, self._N)  # N = off-list/unassigned
        req: List[Tuple[int, int]] = []
        ports: List[int] = []
        for c in pod.spec.containers:
            for name, q in c.resources.limits.items():
                req.append((self._resource_col(name),
                            _preds.resource_value(name, q)))
            if i < self._N:
                for cp in c.ports:
                    if cp.host_port:
                        ports.append(self._port_col(cp.host_port))
        pds: List[int] = []
        if i < self._N:
            for v in pod.spec.volumes:
                if v.source.gce_persistent_disk is not None:
                    pds.append(self._pd_col(
                        v.source.gce_persistent_disk.pd_name))
        ns_code = self._ns.intern(pod.metadata.namespace)
        svcs = self._index.match(pod.metadata.namespace,
                                 pod.metadata.labels)
        rec = _PodRec(i, req, ports, pds, ns_code, svcs,
                      prio=api.pod_priority(pod), name=pod.metadata.name,
                      ns=pod.metadata.namespace,
                      labels=pod.metadata.labels)
        self._pods[uid] = rec
        if i < self._N:
            self._touch(i)
            for r, amt in req:
                self._score_used[i, r] += amt
            for col in ports:
                self._port_cnt[i, col] += 1
            for col in pds:
                self._pd_cnt[i, col] += 1
            # kube-preempt: O(1) single-element evictable-plane updates
            b = self._band_col(rec.prio)
            for r, amt in req:
                self._evict_cap[i, b, r] += amt
            self._evict_cnt[i, b] += 1
            self.op_counts["evict_writes"] += 1
            self._node_pods.setdefault(i, {})[uid] = rec
        self._count_peer(rec, 1)

    def _remove_pod(self, uid: str) -> None:
        rec = self._pods.pop(uid)
        i = rec.host_idx
        if i < self._N:
            self._touch(i)
            for r, amt in rec.req:
                self._score_used[i, r] -= amt
            for col in rec.ports:
                self._port_cnt[i, col] -= 1
            for col in rec.pds:
                self._pd_cnt[i, col] -= 1
            b = self._band_col(rec.prio)
            for r, amt in rec.req:
                self._evict_cap[i, b, r] -= amt
            self._evict_cnt[i, b] -= 1
            self.op_counts["evict_writes"] += 1
            node = self._node_pods.get(i)
            if node is not None:
                node.pop(uid, None)
        self._count_peer(rec, -1)

    # -- kube-preempt victim materialization --------------------------------
    def resident_on(self, node_idx: int):
        """ResidentPod rows for one node — the per-node registry feed for
        models/preempt.assign_victims (O(pods on the node), not
        O(cluster))."""
        from kubernetes_tpu.models.preempt import ResidentPod
        return [ResidentPod(uid, rec.name, rec.ns, rec.host_idx, rec.prio)
                for uid, rec in self._node_pods.get(node_idx, {}).items()]

    # -- kube-slipstream checkpoint / journal replay ------------------------
    # Everything the encoder mutates between waves, grouped by how it must
    # be captured. Arrays mutate IN PLACE (+=/grow) and are copied; lists
    # and dicts are reassigned or mutated and get shallow copies; _PodRec
    # values and api objects are immutable post-construction and shared
    # copy-on-write across every checkpoint. op_counts is deliberately NOT
    # captured: it counts operations performed, and a restore does not
    # un-perform them.
    _CKPT_ARRAYS = ("_cap", "_advertised", "_score_used", "_port_cnt",
                    "_pd_cnt", "_node_sel", "_extra_ok", "_score_static",
                    "_node_zone", "_evict_cap", "_evict_cnt")
    _CKPT_LISTS = ("_nodes_key", "_svc_key", "_services", "_resource_names",
                   "_node_names", "_node_labels")
    _CKPT_DICTS = ("_rix", "_node_index")
    # the service index is never written once built: shared, like _PodRec
    _CKPT_SCALARS = ("_N", "_band_min", "_preempt_emitted", "_zone_V",
                     "_index", "_g_seen", "_g_wide")
    _CKPT_VOCABS = ("_ports", "_sels", "_pds", "_ns", "_bands")

    def checkpoint(self) -> dict:
        """Capture the resident planes + sticky vocabularies + per-node pod
        registry as an opaque restore() token (kube-slipstream journal
        replay: scheduler/tpu_batch.py restores the last checkpoint and
        replays the modeler changelog instead of re-encoding the cluster).
        Pod records and cluster objects are shared copy-on-write; the
        numpy planes are memcpy'd (milliseconds at planet shape). The
        checkpoint is immutable with respect to later encoder mutation
        and stays restorable any number of times. Raises ValueError
        before the first wave established resident planes."""
        if self._nodes_key is None:
            raise ValueError("nothing resident: encode a wave before "
                             "checkpointing")
        st: dict = {}
        for a in self._CKPT_ARRAYS:
            st[a] = getattr(self, a).copy()
        for a in self._CKPT_LISTS:
            st[a] = list(getattr(self, a))
        for a in self._CKPT_DICTS:
            st[a] = dict(getattr(self, a))
        for a in self._CKPT_SCALARS:
            st[a] = getattr(self, a)
        for a in self._CKPT_VOCABS:
            st[a] = dict(getattr(self, a).index)
        st["_pods"] = dict(self._pods)
        st["_node_pods"] = {i: dict(d) for i, d in self._node_pods.items()}
        st["_peers"] = {k: dict(d) for k, d in self._peers.items()}
        st["_zone_peers"] = {k: z.copy()
                             for k, z in self._zone_peers.items()}
        return st

    def restore(self, ckpt: dict) -> None:
        """Reset the encoder to a checkpoint() state wholesale — including
        dropping any pods applied since. The checkpoint itself is
        re-copied, so it remains valid for further restores."""
        for a in self._CKPT_ARRAYS:
            setattr(self, a, ckpt[a].copy())
        for a in self._CKPT_LISTS:
            setattr(self, a, list(ckpt[a]))
        for a in self._CKPT_DICTS:
            setattr(self, a, dict(ckpt[a]))
        for a in self._CKPT_SCALARS:
            setattr(self, a, ckpt[a])
        for a in self._CKPT_VOCABS:
            v = _Vocab()
            v.index = dict(ckpt[a])
            setattr(self, a, v)
        self._pods = dict(ckpt["_pods"])
        self._node_pods = {i: dict(d)
                           for i, d in ckpt["_node_pods"].items()}
        self._peers = {k: dict(d) for k, d in ckpt["_peers"].items()}
        self._zone_peers = {k: z.copy()
                            for k, z in ckpt["_zone_peers"].items()}
        self._new_epoch("restore")

    def resident_fingerprint(self) -> tuple:
        """Order-stable digest of every resident plane + the pod registry.
        Bit-equal states (same vocab order, same planes, same pods at the
        same hosts) produce equal fingerprints. The KTPU_DEBUG replay gate
        compares the fingerprint after a journal replay against the one
        after a full diff-walk over the authoritative list: equality
        proves the replay reconstructed the exact causal state (the walk
        found nothing to fix)."""
        import zlib
        parts = []
        for a in self._CKPT_ARRAYS:
            arr = getattr(self, a)
            parts.append((a, arr.shape, str(arr.dtype),
                          zlib.crc32(np.ascontiguousarray(arr).tobytes())))
        for a in self._CKPT_VOCABS:
            parts.append((a, tuple(getattr(self, a).index.items())))
        parts.append(("_pods", tuple(sorted(
            (uid, rec.host_idx, rec.prio) for uid, rec in
            self._pods.items()))))
        parts.append(("_peers", tuple(sorted(
            (key, tuple(sorted(at.items())))
            for key, at in self._peers.items()))))
        parts.append(("_services", tuple(self._svc_key or ())))
        parts.append(("scalars", self._N, self._band_min,
                      self._preempt_emitted, self._zone_V,
                      tuple(self._resource_names)))
        return tuple(parts)

    def fill_dims(self) -> dict:
        """True (unpadded) occupancy of the pow-2-bucketed vocabulary
        axes, counted in vocabulary entries and stated in the axis units
        of the device inputs: port/pd sets pack 32 entries per uint32
        word, so one port is 1/32 of a word and not a word. The prewarm
        fill trigger (solver/prewarm.py) compares these against the
        compiled bucket so the next bucket's program compiles BEFORE
        growth crosses the boundary. Axes whose true occupancy the
        encoder does not track are omitted — absent keys never trigger.
        ``G`` is the most groups one wave has named, while the group axis
        stands at its floor; once it follows the pod bucket
        (``_group_bucket``) it is no axis of its own and is left out."""
        dims = {
            "Wp": len(self._ports) / 32,
            "Wd": len(self._pds) / 32,
            "Ks": len(self._sels),
            "B": len(self._bands),
        }
        if not self._g_wide:
            dims["G"] = self._g_seen
        return dims

    # -- wave encode --------------------------------------------------------
    def encode(self, nodes: Sequence[api.Node],
               existing_pods: Sequence[api.Pod],
               pending_pods: Sequence[api.Pod],
               services: Sequence[api.Service] = (),
               pad_pods: bool = True) -> ClusterSnapshot:
        services = list(services)
        if self._nodes_changed(nodes):
            self._rebuild_nodes(nodes, existing_pods, services)
        else:
            self._sync_services(services)
            cur = {}
            for p in existing_pods:
                cur[p.metadata.uid] = p
            cached = self._pods
            removed = [u for u in cached if u not in cur]
            for u in removed:
                self._remove_pod(u)
            for u, p in cur.items():
                rec = cached.get(u)
                if rec is None:
                    self._add_pod(p)
                elif rec.host_idx != self._node_index.get(p.status.host,
                                                          self._N):
                    self._remove_pod(u)   # host changed: re-account
                    self._add_pod(p)
            # the greedy fit accumulators follow the order of this list
            # where a node overflows: the touched rows do not say that
            self._new_epoch("full_encode")
        return self._build(existing_pods, pending_pods, pad_pods)

    def encode_delta(self, nodes: Sequence[api.Node],
                     upserted: Sequence[api.Pod],
                     removed: Sequence[api.Pod],
                     pending_pods: Sequence[api.Pod],
                     services: Sequence[api.Service] = (),
                     pad_pods: bool = True) -> Optional[ClusterSnapshot]:
        """O(changed + pending) wave encode: apply a SimpleModeler.delta
        (upserts first, then removes — see its contract) instead of
        re-walking the whole existing-pod list. Returns None — caller must
        fall back to encode() with the full list — when the node planes
        changed, or when some node's usage exceeds its capacity:
        the greedy fit accumulators are existing-LIST-order exact there
        (snapshot.greedy_fit_accumulators), and only the full walk carries
        that order."""
        services = list(services)
        if self._nodes_key is None or self._nodes_changed(nodes):
            return None
        self._sync_services(services)
        for p in upserted:
            rec = self._pods.get(p.metadata.uid)
            host = self._node_index.get(p.status.host, self._N)
            if rec is None:
                self._add_pod(p)
            elif rec.host_idx != host:
                self._remove_pod(p.metadata.uid)
                self._add_pod(p)
        for p in removed:
            if p.metadata.uid in self._pods:
                self._remove_pod(p.metadata.uid)
        # overflow anywhere -> the order-exact slow path is required
        R = self._score_used.shape[1]
        cap = self._cap if self._cap.shape[1] == R else \
            np.pad(self._cap, ((0, 0), (0, R - self._cap.shape[1])))
        unconstrained = (cap == 0) & (np.arange(R) < 2)[None, :]
        if not (unconstrained | (self._score_used <= cap)).all():
            return None
        return self._build(None, pending_pods, pad_pods)

    def _wave_groups(self, pending_pods, pod_ns: np.ndarray, Ppad: int):
        """The service groups of one wave: each pending pod's services by
        the index, one row for each distinct group a pod names (the FIRST
        service that selects it, in its namespace: ServiceSpread's "just
        use the first service", spreading.go:44), every row filled from
        the sparse peer counts in O(its peers). -> (pod_gid [Ppad], -1
        without a service; pod_group_member [Ppad, G]; group_counts
        [G, N+1]; zone_counts0 [A, G, V]). A pod is a member of every row
        whose service selects it, so its commit counts toward each."""
        N, P = self._N, len(pending_pods)
        with tracing.phase("wave.encode.groups", metrics.wave_parts(),
                           "encode.groups"):
            pod_gid = np.full(Ppad, -1, np.int32)
            rows: Dict[Tuple[int, int], int] = {}
            named: List[Tuple[int, int, Tuple[int, ...]]] = []
            if self._services:
                match = self._index.match
                for j, p in enumerate(pending_pods):
                    svcs = match(p.metadata.namespace, p.metadata.labels)
                    if svcs:
                        ns = int(pod_ns[j])
                        pod_gid[j] = rows.setdefault((ns, svcs[0]),
                                                     len(rows))
                        named.append((j, ns, svcs))
            G = self._group_bucket(len(rows), Ppad)
            member = np.zeros((Ppad, G), bool)
            for j, ns, svcs in named:
                member[j, pod_gid[j]] = True
                for si in svcs[1:]:
                    row = rows.get((ns, si))
                    if row is not None:
                        member[j, row] = True
            group_counts = np.zeros((G, N + 1), np.int32)
            A = self._node_zone.shape[0]
            zone_counts0 = np.zeros((A, G, self._zone_V), np.int32)
            peered = np.zeros(G, bool)
            for key, row in rows.items():
                at = self._peers.get(key)
                if at:
                    peered[row] = True
                    group_counts[row, np.fromiter(at, np.int64, len(at))] = \
                        np.fromiter(at.values(), np.int32, len(at))
                    zones = self._zone_peers.get(key)
                    if zones is not None:
                        zone_counts0[:, row, :] = zones
            wave_groups().inc(by=len(rows))
            if named:
                peered_pods().inc(
                    by=int(peered[[pod_gid[j] for j, _, _ in named]].sum()))
        return pod_gid, member, group_counts, zone_counts0

    def _build(self, existing_pods, pending_pods, pad_pods) -> ClusterSnapshot:
        """The pending-pod pass + snapshot assembly over the resident
        planes. ``existing_pods`` feeds the greedy overflow walk; None
        (delta path) is only legal when no node overflows — encode_delta
        checked before calling."""
        N = self._N
        P = len(pending_pods)
        Ppad = _pow2_pad(P, minimum=1) if pad_pods else max(P, 0)
        R0 = len(self._resource_names)

        # -- the per-pod half: one pass over the pending pods (sticky vocabs;
        # may grow columns), their port / selector / disk planes, their
        # service groups, tie-break keys and gang runs. What is left of the
        # encode after it is per node.
        with tracing.phase("wave.encode.pods", metrics.wave_parts(),
                           "encode.pods"):
            req = np.zeros((Ppad, R0), np.int64)
            # (row, rcol, amt) of a resource column grown in this pass
            grow_req: List[Tuple[int, int, int]] = []
            pp_ij: List[Tuple[int, int]] = []
            ps_ij: List[Tuple[int, int]] = []
            pg_ij: List[Tuple[int, int]] = []
            pod_host_idx = np.full(Ppad, -2, np.int32)
            pod_host_idx[:P] = -1
            pod_prio = np.zeros(Ppad, np.int32)
            pod_can_preempt = np.zeros(Ppad, bool)  # padding never preempts
            pod_names: List[str] = []
            pod_ns = np.zeros(P, np.int32)
            for j, p in enumerate(pending_pods):
                meta = p.metadata
                pod_names.append(f"{meta.namespace}/{meta.name}")
                pod_ns[j] = self._ns.intern(meta.namespace)
                for c in p.spec.containers:
                    for name, q in c.resources.limits.items():
                        r = self._rix.get(name)
                        amt = _preds.resource_value(name, q)
                        if r is None:
                            grow_req.append(
                                (j, self._resource_col(name), amt))
                        elif r < R0:
                            req[j, r] += amt
                        else:
                            grow_req.append((j, r, amt))
                    for cp in c.ports:
                        if cp.host_port:
                            pp_ij.append((j, self._port_col(cp.host_port)))
                for kv in (p.spec.node_selector or {}).items():
                    ps_ij.append((j, self._sel_col(kv)))
                for v in p.spec.volumes:
                    if v.source.gce_persistent_disk is not None:
                        pg_ij.append((j, self._pd_col(
                            v.source.gce_persistent_disk.pd_name)))
                if p.spec.host:
                    pod_host_idx[j] = self._node_index.get(p.spec.host, -2)
                pod_prio[j] = api.pod_priority(p)
                pod_can_preempt[j] = api.pod_can_preempt(p)
            R = len(self._resource_names)
            if R > R0:
                req = np.pad(req, ((0, 0), (0, R - R0)))
            for row, r, amt in grow_req:
                req[row, r] += amt

            def scatter(pairs, rows, cols, dtype=bool):
                out = np.zeros((rows, cols), dtype)
                if pairs:
                    idx = np.asarray(pairs, np.int64)
                    out[idx[:, 0], idx[:, 1]] = True
                return out

            Kp, Ks, Kd = self._ports.cap, self._sels.cap, self._pds.cap
            pod_ports = scatter(pp_ij, Ppad, Kp)
            pod_sel = scatter(ps_ij, Ppad, Ks)
            pod_pds = scatter(pg_ij, Ppad, Kd)

            # the pods that carry a node selector, a host port or (below)
            # the membership of a service
            constrained = pod_sel[:P].any(axis=1) | pod_ports[:P].any(axis=1)

            pod_gid, member, group_counts, zone_counts0 = \
                self._wave_groups(pending_pods, pod_ns, Ppad)
            constrained |= pod_gid[:P] >= 0
            constrained_pods().inc(by=int(constrained.sum()))

            tie = _fnv1a64_batch([pod_tie_break_key(p)
                                  for p in pending_pods])
            tie_hi = np.zeros(Ppad, np.int64)
            tie_lo = np.zeros(Ppad, np.int64)
            tie_hi[:P] = (tie >> np.uint64(32)).astype(np.int64)
            tie_lo[:P] = (tie & np.uint64(0xFFFFFFFF)).astype(np.int64)

            rid, run_start = gang.pod_run_ids(pending_pods)
            pod_rid = np.full(Ppad, -1, np.int32)
            pod_rid[:P] = rid
            pod_run_start = np.ones(Ppad, bool)
            pod_run_start[:P] = run_start

        # -- fit accumulators (greedy only for genuine overflow) ------------
        cap = self._cap
        if cap.shape[1] < R:
            cap = np.pad(cap, ((0, 0), (0, R - cap.shape[1])))
            self._cap = cap
        if self._advertised.shape[1] < R:
            self._advertised = np.pad(
                self._advertised, ((0, 0), (0, R - self._advertised.shape[1])))
        score_used = self._score_used
        if score_used.shape[1] < R:
            score_used = np.pad(score_used, ((0, 0), (0, R - score_used.shape[1])))
            self._score_used = score_used
        def recs_in_list_order():
            # current list order == what the oracle's full encode would see.
            # The delta path passes existing_pods=None: legal because it
            # bailed to the full path before any node overflowed, and
            # greedy_fit_accumulators only consumes this on overflow.
            for p in existing_pods or ():
                rec = self._pods.get(p.metadata.uid)
                if rec is None:
                    continue
                e_req = np.zeros(R, np.int64)
                for r, amt in rec.req:
                    e_req[r] += amt
                yield rec.host_idx, e_req

        fit_used, fit_exceeded = greedy_fit_accumulators(
            cap, score_used, recs_in_list_order())

        # -- kube-preempt planes (sticky emit gate) -------------------------
        if not self._preempt_emitted and len(self._bands) and P \
                and int(pod_prio[:P].max()) > self._band_min:
            self._preempt_emitted = True
            self._new_epoch("preempt_gate")
        if self._preempt_emitted:
            from kubernetes_tpu.models import preempt as _preempt
            Bc = self._bands.cap
            band_prio = np.full(Bc, _preempt.BAND_EMPTY, np.int32)
            for v, b in self._bands.index.items():
                band_prio[b] = v
            evict_cap = self._evict_cap[:, :Bc, :R].copy()
            evict_cnt = self._evict_cnt[:, :Bc].copy()
            if evict_cap.shape[2] < R:
                evict_cap = np.pad(
                    evict_cap, ((0, 0), (0, 0),
                                (0, R - evict_cap.shape[2])))
            if _DEBUG_VERIFY_EVICT:
                e_host = np.array([rec.host_idx
                                   for rec in self._pods.values()])
                e_prio = np.array([rec.prio
                                   for rec in self._pods.values()])
                e_req = np.zeros((len(self._pods), R), np.int64)
                for k, rec in enumerate(self._pods.values()):
                    for r, amt in rec.req:
                        e_req[k, r] += amt
                want_cap, want_cnt = _preempt.derive_evict_planes(
                    e_host, e_prio, e_req, band_prio, N)
                assert np.array_equal(want_cap, evict_cap) and \
                    np.array_equal(want_cnt, evict_cnt), (
                        "resident evictable planes diverged from the "
                        "derive_evict_planes from-scratch twin — the "
                        "O(bands) incremental maintenance is out of sync")
        else:
            band_prio = np.zeros(0, np.int32)
            evict_cap = np.zeros((N, 0, R), np.int64)
            evict_cnt = np.zeros((N, 0), np.int32)

        seq = self._touch_base + len(self._touch_log)
        return ClusterSnapshot(
            node_names=self._node_names,
            resource_names=list(self._resource_names),
            cap=cap, advertised=self._advertised,
            fit_used=fit_used, fit_exceeded=fit_exceeded,
            score_used=score_used,
            node_ports=self._port_cnt > 0,
            node_sel=self._node_sel,
            node_pds=self._pd_cnt > 0,
            node_extra_ok=self._extra_ok.copy(),
            pod_names=pod_names,
            req=req,
            pod_ports=pod_ports, pod_sel=pod_sel, pod_pds=pod_pds,
            pod_host_idx=pod_host_idx, tie_hi=tie_hi, tie_lo=tie_lo,
            pod_gid=pod_gid, pod_group_member=member,
            group_counts=group_counts,
            pod_rid=pod_rid, pod_run_start=pod_run_start,
            score_static=self._score_static,
            node_zone=self._node_zone,
            zone_counts0=zone_counts0,
            pod_prio=pod_prio, pod_can_preempt=pod_can_preempt,
            band_prio=band_prio, evict_cap=evict_cap, evict_cnt=evict_cnt,
            policy=self.policy,
            w_least_requested=self.policy.w_lr,
            w_spreading=self.policy.w_spread,
            w_equal=self.policy.w_equal,
            resident_epoch=self._epoch, resident_why=self._epoch_why,
            resident_seq=seq,
            touched_since=functools.partial(self._touched,
                                            epoch=self._epoch, upto=seq),
        )
