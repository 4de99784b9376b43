"""ClusterSnapshot — dense-tensor encoding of scheduler state.

The TPU analog of the reference's per-cycle ``MapPodsToMachines`` pivot
(ref: pkg/scheduler/predicates.go:354-375): one host-side pass encodes nodes,
existing pods, and the pending-pod batch into fixed-shape arrays the batch
solver (kubernetes_tpu.models.batch_solver) consumes in a single compiled
call.

Exactness over hashing: label selectors, host ports, GCE PD names, and
affinity label values are interned into small per-batch vocabularies built
from the pending pods, so the "does pod p's selector accept node n" check is
an exact boolean matmul — no hash collisions to reconcile with the serial
oracle.

Encoded predicate state mirrors predicates.go exactly:
- resources: two accumulators per node — the greedy-fitting usage + exceeded
  flag (CheckPodsExceedingCapacity semantics, :104-124) used by the Filter,
  and the sum over ALL pods used by LeastRequested scoring
  (priorities.go:41-75, which does not skip exceeding pods);
- ports: vocabulary over host ports observed anywhere (getUsedPorts :340);
- service spreading: per (namespace, first-matching-service) group counts by
  host, plus one overflow bucket for unassigned/unknown hosts — the
  reference counts those toward maxCount too (spreading.go:62-68). The
  group axis is padded to a power of two (recompile-friendly buckets); a
  wave may span arbitrarily many services.

Policy extensions (models/policy.BatchPolicy):
- CheckNodeLabelPresence folds into ``node_extra_ok`` (static per node);
- NodeLabelPriority folds into ``score_static`` (static additive score);
- CheckServiceAffinity: per-label value codes for nodes, the pod's
  node-selector-pinned codes, and per-group anchor state (the first
  committed service peer's node values — predicates.go:238-324);
- ServiceAntiAffinity: per-config node zone codes (spreading.go:104-168).

Everything host-side is vectorized numpy — one Python pass over each pod
list to pull fields out of the object graph, then bulk array ops; there are
no per-(pod x service) or per-(group x pod) Python loops.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from kubernetes_tpu.api import types as api
from kubernetes_tpu.models import gang
from kubernetes_tpu.models.policy import BatchPolicy, DEFAULT_BATCH_POLICY
from kubernetes_tpu.scheduler import predicates as _preds
from kubernetes_tpu.scheduler.generic import (
    FNV64_OFFSET,
    FNV64_PRIME,
    pod_tie_break_key,
)

__all__ = ["ClusterSnapshot", "encode_snapshot", "greedy_fit_accumulators"]

# KTPU_DEBUG=1: recompute every _ktpu_rows cache hit from the object graph
# and assert it matches — catches in-place PodSpec mutation, which the
# cache's correctness forbids (see container_rows + runtime/clone.py)
_DEBUG_VERIFY_ROWS = os.environ.get("KTPU_DEBUG", "") not in ("", "0")


def _fnv1a64_batch(keys: List[str]) -> np.ndarray:
    """Vectorized FNV-1a-64 over a batch of strings (same results as
    scheduler.generic.fnv1a64, which stays the serial-oracle twin). The
    per-byte dependency chain runs over the max string length — a dozen
    numpy passes over [P] instead of 10k Python loops."""
    if not keys:
        return np.zeros(0, np.uint64)
    bs = [k.encode("utf-8") for k in keys]
    maxlen = max(len(b) for b in bs)
    if maxlen == 0:
        return np.full(len(bs), FNV64_OFFSET, np.uint64)
    buf = np.frombuffer(b"".join(b.ljust(maxlen, b"\0") for b in bs),
                        np.uint8).reshape(len(bs), maxlen)
    lens = np.array([len(b) for b in bs])
    h = np.full(len(bs), FNV64_OFFSET, np.uint64)
    prime = np.uint64(FNV64_PRIME)
    for c in range(maxlen):
        nh = (h ^ buf[:, c].astype(np.uint64)) * prime  # wraps mod 2^64
        h = np.where(c < lens, nh, h)
    return h

def greedy_fit_accumulators(cap: np.ndarray, score_used: np.ndarray,
                            pods_in_order) -> Tuple[np.ndarray, np.ndarray]:
    """Greedy Filter accumulators (CheckPodsExceedingCapacity :104-124):
    when a node's total existing usage fits its capacity, every prefix fit
    too — the greedy result equals the sum and nothing exceeded. Only the
    (rare) overflowing nodes walk ``pods_in_order`` — an iterable of
    (host_idx, req_vec[R]) in existing-list order (host_idx >= N =
    off-list). Shared by the full and incremental encoders so the
    order-exact rule can never drift between them. Per-dim fit rule is
    predicates.dim_fits: cpu/memory zero-capacity is unconstrained;
    extended dims are strict."""
    N, R = cap.shape
    fit_used = score_used.copy()
    fit_exceeded = np.zeros(N, bool)
    is_core = np.arange(R) < 2
    unconstrained = (cap == 0) & is_core[None, :]
    all_fit = (unconstrained | (score_used <= cap)).all(axis=1)
    if not all_fit.all():
        slow = set(np.nonzero(~all_fit)[0].tolist())
        per_host: Dict[int, np.ndarray] = {
            i: np.zeros(R, np.int64) for i in slow}
        for i, e_req in pods_in_order:
            i = int(i)
            if i not in per_host:
                continue
            used = per_host[i]
            if bool((unconstrained[i] | (cap[i] - used >= e_req)).all()):
                per_host[i] = used + e_req
            else:
                fit_exceeded[i] = True
        for i, used in per_host.items():
            fit_used[i] = used
    return fit_used, fit_exceeded


def _pow2_pad(n: int, minimum: int = 8) -> int:
    """Next power of two >= max(n, minimum) — bounds the number of distinct
    compiled shapes as the group count varies wave to wave."""
    out = minimum
    while out < n:
        out *= 2
    return out


@dataclass
class ClusterSnapshot:
    """All arrays are numpy; the solver moves them to device."""

    node_names: List[str]
    # R-dimensional resource planes (int64: memory bytes exceed int32).
    # resource_names[0:2] is always [cpu, memory] (reference parity), then
    # node-advertised extras, then request-only dims (constrain but never
    # score). ``advertised`` records capacity-key PRESENCE per node — a
    # zero-quantity advertisement still widens the serial LeastRequested
    # universe (resource_universe iterates names), so the solver's per-pod
    # divisor must see it even though cap == 0.
    resource_names: List[str]
    cap: np.ndarray              # [N, R] i64 (cpu col in milli-units)
    advertised: np.ndarray       # [N, R] bool — capacity key present
    fit_used: np.ndarray         # [N, R] i64 greedy-fitting usage (Filter)
    fit_exceeded: np.ndarray     # [N] bool — an existing pod already didn't fit
    score_used: np.ndarray       # [N, R] i64 all-pods usage (Score)
    # vocab-interned boolean features
    node_ports: np.ndarray       # [N, K] bool
    node_sel: np.ndarray         # [N, K2] bool — node has (key,value) label
    node_pds: np.ndarray         # [N, K3] bool
    node_extra_ok: np.ndarray    # [N] bool — NodeLabelPresence + caller mask
    # pending pods
    pod_names: List[str]
    req: np.ndarray              # [P, R] i64
    pod_ports: np.ndarray        # [P, K] bool
    pod_sel: np.ndarray          # [P, K2] bool — required (key,value) pairs
    pod_pds: np.ndarray          # [P, K3] bool
    pod_host_idx: np.ndarray     # [P] i32: -1 unset, -2 host not in node list
    tie_hi: np.ndarray           # [P] i64 — fnv1a64(pod key) >> 32
    tie_lo: np.ndarray           # [P] i64 — fnv1a64(pod key) & 0xffffffff
    # service spreading groups (axis padded to a power of two)
    pod_gid: np.ndarray          # [P] i32, -1 = no service
    pod_group_member: np.ndarray  # [P, G] bool — pod's labels match group's selector
    group_counts: np.ndarray     # [G, N+1] i32 (slot N: unassigned/unknown hosts)
    # gang (PodGroup) runs — models/gang.py; rid -1 = singleton
    pod_rid: np.ndarray = None       # [P] i32 run id
    pod_run_start: np.ndarray = None  # [P] bool — checkpoint marker
    # policy extensions (minimal shapes when the policy doesn't use them)
    score_static: np.ndarray = None    # [N] i32 — NodeLabelPriority terms
    node_aff_vals: np.ndarray = None   # [N, L] i32 value codes, -1 absent
    pod_aff_static: np.ndarray = None  # [P, L] i32 codes, -2 unspecified
    anchor_vals0: np.ndarray = None    # [G, L] i32 — initial anchor values
    has_anchor0: np.ndarray = None     # [G] bool
    node_zone: np.ndarray = None       # [A, N] i32 zone codes, -1 unlabeled
    # per-group per-zone initial peer totals [A, G, V]; None = derive from
    # node_zone x group_counts (full encoder). The incremental encoder
    # maintains this plane resident — O(changed) per bind/delete — and the
    # solver seeds its scan carry from it (batch_solver.derive_zone_counts
    # is the authoritative definition).
    zone_counts0: np.ndarray = None
    # kube-preempt planes (models/preempt.py). B == 0 disables the whole
    # preemption sub-program (the emit gate: no pending pod sits strictly
    # above any resident band), compiling the exact legacy scan. The
    # evictable planes are band-granular aggregates of resident pods'
    # request vectors, maintained O(bands) per delta by the incremental
    # encoder; derive_evict_planes is the from-scratch twin.
    pod_prio: np.ndarray = None        # [P] i32 resolved priorities
    pod_can_preempt: np.ndarray = None  # [P] bool (PreemptionPolicy!=Never)
    band_prio: np.ndarray = None       # [B] i32 values, BAND_EMPTY padded
    evict_cap: np.ndarray = None       # [N, B, R] i64 evictable capacity
    evict_cnt: np.ndarray = None       # [N, B] i32 evictable pod counts
    policy: BatchPolicy = field(default_factory=lambda: DEFAULT_BATCH_POLICY)
    # priority weights (kept for back-compat; mirror policy)
    w_least_requested: int = 1
    w_spreading: int = 1
    w_equal: int = 0
    # residency (models/resident.py), set by the incremental encoder only:
    # the epoch inside which the node planes of two snapshots differ by the
    # rows the encoder touched and nothing else (None: never patch), what
    # began it, this snapshot's place in the touched-row log, and
    # ``touched_since(seq)`` -> the rows written in [seq, resident_seq), or
    # None where the log cannot say
    resident_epoch: Optional[int] = None
    resident_why: str = ""
    resident_seq: int = 0
    touched_since: Optional[Callable] = None

    @property
    def n_nodes(self) -> int:
        return len(self.node_names)

    @property
    def n_pods(self) -> int:
        return len(self.pod_names)

    @property
    def has_gangs(self) -> bool:
        return self.pod_rid is not None and bool((self.pod_rid >= 0).any())


def _label_items(meta_labels: Optional[Dict[str, str]]):
    return (meta_labels or {}).items()


def encode_snapshot(nodes: Sequence[api.Node], existing_pods: Sequence[api.Pod],
                    pending_pods: Sequence[api.Pod],
                    services: Sequence[api.Service] = (),
                    node_extra_ok: Optional[np.ndarray] = None,
                    policy: Optional[BatchPolicy] = None) -> ClusterSnapshot:
    """Encode one scheduling wave. Node order defines the tie-break order and
    must match what the serial oracle sees."""
    policy = policy or DEFAULT_BATCH_POLICY
    N, P, E = len(nodes), len(pending_pods), len(existing_pods)
    node_index = {n.metadata.name: i for i, n in enumerate(nodes)}

    # -- capacities: R-dimensional planes -----------------------------------
    # resource universe and value canonicalization shared with the serial
    # path (scheduler.predicates.resource_universe / resource_value): the
    # scored dims (cpu, memory, node-advertised extras) come first; dims
    # only requested by pods are appended — they constrain (dim_fits) but
    # score zero everywhere and never widen the LeastRequested divisor.
    scored = _preds.resource_universe(nodes)
    seen = set(scored)
    request_only: List[str] = []
    # one traversal extracts each pod's (resource, value) rows, its host
    # ports, AND the request-only dims; the main passes below then never
    # re-walk the container object graph (the graph walk, not the
    # arithmetic, dominates host encode time at 10k-pod waves)
    CPU = api.ResourceCPU

    def container_rows(pods):
        # Derived rows cache on the spec object: a PodSpec's containers are
        # immutable once stored (the repo-wide read-only-store-objects
        # invariant — mutations go through deep_clone, which drops
        # undeclared attributes), so the (resource, value) rows and host
        # ports are computed once per pod LIFETIME, not once per wave. A
        # live scheduler re-encodes the same reflector-store objects every
        # wave, so this is exactly the hit rate production sees. The
        # per-wave resource-universe bookkeeping (seen/request_only) still
        # runs over the cached rows — it is wave-local.
        limits, ports = [], []

        def derive(spec):
            lr, pr = [], []
            for c in spec.containers:
                for name, q in c.resources.limits.items():
                    lr.append((name, q.milli_value() if name == CPU
                               else q.int_value()))
                for cp in c.ports:
                    if cp.host_port:
                        pr.append(cp.host_port)
            return (lr, pr)

        for p in pods:
            spec = p.spec
            cached = spec.__dict__.get("_ktpu_rows")
            if cached is None:
                cached = derive(spec)
                spec.__dict__["_ktpu_rows"] = cached
            elif _DEBUG_VERIFY_ROWS:
                fresh = derive(spec)
                assert fresh == cached, (
                    f"_ktpu_rows cache stale for pod "
                    f"{p.metadata.namespace}/{p.metadata.name}: cached "
                    f"{cached!r} != recomputed {fresh!r} — a PodSpec was "
                    f"mutated in place after encoding (mutations must go "
                    f"through runtime.clone.deep_clone, which drops the "
                    f"cache)")
            lr, pr = cached
            for name, _v in lr:
                if name not in seen:
                    seen.add(name)
                    request_only.append(name)
            limits.append(lr)
            ports.append(pr)
        return limits, ports

    pend_limits, pend_ports = container_rows(pending_pods)
    exist_limits, exist_ports = container_rows(existing_pods)
    resource_names = scored + sorted(request_only)
    R = len(resource_names)
    rindex = {name: r for r, name in enumerate(resource_names)}
    cap = np.zeros((N, R), np.int64)
    advertised = np.zeros((N, R), bool)
    for i, n in enumerate(nodes):
        for name, q in (n.spec.capacity or {}).items():
            r = rindex.get(name)
            if r is not None:
                cap[i, r] = _preds.resource_value(name, q)
                advertised[i, r] = True

    # -- service selector vocabulary (needed by the pod passes) -------------
    services = list(services)
    S = len(services)
    svc_vocab: Dict[Tuple[str, str], int] = {}
    ns_codes: Dict[str, int] = {}

    def intern(vocab, key):
        if key not in vocab:
            vocab[key] = len(vocab)
        return vocab[key]

    sv_ij: List[Tuple[int, int]] = []
    for si, s in enumerate(services):
        for kv in (s.spec.selector or {}).items():
            sv_ij.append((si, intern(svc_vocab, kv)))

    # -- pending pods: one Python pass pulls every field --------------------
    port_vocab: Dict[int, int] = {}
    sel_vocab: Dict[Tuple[str, str], int] = {}
    pd_vocab: Dict[str, int] = {}

    req = np.zeros((P, R), np.int64)
    pod_host_idx = np.full(P, -1, np.int32)
    pod_prio = np.zeros(P, np.int32)
    pod_can_preempt = np.ones(P, bool)
    pod_names: List[str] = []
    pp_ij: List[Tuple[int, int]] = []   # (pod, port-vocab) pairs
    ps_ij: List[Tuple[int, int]] = []   # (pod, selector-vocab)
    pg_ij: List[Tuple[int, int]] = []   # (pod, pd-vocab)
    pf_ij: List[Tuple[int, int]] = []   # (pod, service-selector-vocab)
    pod_ns = np.zeros(P, np.int32)
    svc_get = svc_vocab.get
    rindex_get = rindex.get
    node_index_get = node_index.get
    pf_append = pf_ij.append
    pp_append = pp_ij.append
    for j, p in enumerate(pending_pods):
        meta = p.metadata
        spec = p.spec
        pod_names.append(f"{meta.namespace}/{meta.name}")
        pod_ns[j] = intern(ns_codes, meta.namespace)
        lbls = meta.labels
        if lbls:
            for kv in lbls.items():
                t = svc_get(kv)
                if t is not None:
                    pf_append((j, t))
        # limit/port rows pre-extracted (predicates.go:93-101 semantics)
        for name, val in pend_limits[j]:
            r = rindex_get(name)
            if r is not None:
                req[j, r] += val
        for hp in pend_ports[j]:
            pp_append((j, intern(port_vocab, hp)))
        if spec.node_selector:
            for kv in spec.node_selector.items():
                ps_ij.append((j, intern(sel_vocab, kv)))
        for v in spec.volumes:
            if v.source.gce_persistent_disk is not None:
                pg_ij.append((j, intern(pd_vocab,
                                        v.source.gce_persistent_disk.pd_name)))
        if spec.host:
            pod_host_idx[j] = node_index_get(spec.host, -2)
        pod_prio[j] = api.pod_priority(p)
        pod_can_preempt[j] = api.pod_can_preempt(p)
    pod_rid, pod_run_start = gang.pod_run_ids(pending_pods)
    tie = _fnv1a64_batch([pod_tie_break_key(p) for p in pending_pods])
    tie_hi = (tie >> np.uint64(32)).astype(np.int64)
    tie_lo = (tie & np.uint64(0xFFFFFFFF)).astype(np.int64)

    # pow-2 buckets on every variable axis (like the group axis below), so
    # churning vocabularies re-use at most log2 distinct compiled shapes
    K = _pow2_pad(len(port_vocab))
    K2 = _pow2_pad(len(sel_vocab))
    K3 = _pow2_pad(len(pd_vocab))

    def scatter_true(pairs, rows, cols) -> np.ndarray:
        out = np.zeros((rows, cols), bool)
        if pairs:
            idx = np.asarray(pairs, np.int64)
            out[idx[:, 0], idx[:, 1]] = True
        return out

    pod_ports = scatter_true(pp_ij, P, K)
    pod_sel = scatter_true(ps_ij, P, K2)
    pod_pds = scatter_true(pg_ij, P, K3)

    # -- node label plane for the selector vocabulary -----------------------
    node_sel = np.zeros((N, K2), bool)
    for i, n in enumerate(nodes):
        for kv in _label_items(n.metadata.labels):
            k = sel_vocab.get(kv)
            if k is not None:
                node_sel[i, k] = True

    # -- existing pods: one Python pass, then bulk accumulation -------------
    e_host = np.full(E, N, np.int64)      # N = unknown/unassigned slot
    e_req = np.zeros((E, R), np.int64)
    e_prio = np.zeros(E, np.int32)
    np_ij: List[Tuple[int, int]] = []     # (node, port-vocab)
    nd_ij: List[Tuple[int, int]] = []     # (node, pd-vocab)
    ef_ij: List[Tuple[int, int]] = []     # (pod, service-selector-vocab)
    e_ns = np.full(E, -9, np.int32)       # unseen namespaces can't match
    ns_get = ns_codes.get
    port_get = port_vocab.get
    ef_append = ef_ij.append
    for e, p in enumerate(existing_pods):
        meta = p.metadata
        code = ns_get(meta.namespace)
        if code is not None:
            e_ns[e] = code
        lbls = meta.labels
        if lbls:
            for kv in lbls.items():
                t = svc_get(kv)
                if t is not None:
                    ef_append((e, t))
        i = node_index_get(p.status.host, -1)
        e_prio[e] = api.pod_priority(p)
        for name, val in exist_limits[e]:
            r = rindex_get(name)
            if r is not None:
                e_req[e, r] += val
        if i < 0:
            continue
        for hp in exist_ports[e]:
            k = port_get(hp)
            if k is not None:
                np_ij.append((i, k))
        e_host[e] = i
        for v in p.spec.volumes:
            if v.source.gce_persistent_disk is not None:
                k = pd_vocab.get(v.source.gce_persistent_disk.pd_name)
                if k is not None:
                    nd_ij.append((i, k))

    node_ports = scatter_true(np_ij, N, K)
    node_pds = scatter_true(nd_ij, N, K3)

    on_node = e_host < N
    score_used = np.zeros((N, R), np.int64)
    np.add.at(score_used, e_host[on_node], e_req[on_node])

    fit_used, fit_exceeded = greedy_fit_accumulators(
        cap, score_used, zip(e_host.tolist(), e_req))

    # -- kube-preempt: priority bands + evictable planes --------------------
    # emit gate (preempt.preemption_possible): the planes (and the extra
    # compiled scan program) ship only when some pending pod sits strictly
    # above some resident priority; every other wave compiles the exact
    # legacy program with B == 0
    from kubernetes_tpu.models import preempt as _preempt
    band_vals = sorted({int(v) for v, on in zip(e_prio, on_node) if on})
    if band_vals and P and \
            int(pod_prio.max(initial=-(2**31))) > band_vals[0]:
        B = _pow2_pad(len(band_vals), minimum=2)
        band_prio = np.full(B, _preempt.BAND_EMPTY, np.int32)
        band_prio[:len(band_vals)] = band_vals
        evict_cap, evict_cnt = _preempt.derive_evict_planes(
            e_host, e_prio, e_req, band_prio, N)
    else:
        band_prio = np.zeros(0, np.int32)
        evict_cap = np.zeros((N, 0, R), np.int64)
        evict_cnt = np.zeros((N, 0), np.int32)

    # -- service groups (vectorized) ---------------------------------------
    # group = (namespace, index of FIRST service whose selector matches the
    # pod) — mirrors ServiceSpread's "just use the first service"
    # (spreading.go:44). Group membership of *any* pod (existing or
    # committed) is: same namespace + selector match.
    T = max(1, len(svc_vocab))
    svc_req = scatter_true(sv_ij, max(1, S), T)[:S] if S else np.zeros((0, T), bool)
    req_cnt = svc_req.sum(axis=1).astype(np.int32)            # [S]
    svc_ns = np.array([(intern(ns_codes, s.metadata.namespace)
                        if s.metadata.namespace else -1) for s in services],
                      np.int32) if S else np.zeros(0, np.int32)

    def feat_matrix(pairs, rows) -> np.ndarray:
        out = np.zeros((max(1, rows), T), np.float32)
        if pairs:
            idx = np.asarray(pairs, np.int64)
            out[idx[:, 0], idx[:, 1]] = 1.0
        return out[:rows]

    group_ids: Dict[Tuple[int, int], int] = {}   # (ns_code, svc_idx) -> gid
    pod_gid = np.full(P, -1, np.int32)
    if S and P:
        pod_feat = feat_matrix(pf_ij, P)                       # [P, T]
        hits = pod_feat @ svc_req.astype(np.float32).T          # [P, S]
        subset_pending = hits == req_cnt[None, :]
        eligible = subset_pending & (req_cnt[None, :] > 0) & \
            ((svc_ns[None, :] == -1) | (svc_ns[None, :] == pod_ns[:, None]))
        has_svc = eligible.any(axis=1)
        first_svc = np.argmax(eligible, axis=1)
        for j in np.nonzero(has_svc)[0]:
            key = (int(pod_ns[j]), int(first_svc[j]))
            if key not in group_ids:
                group_ids[key] = len(group_ids)
            pod_gid[j] = group_ids[key]

    G_real = len(group_ids)
    G = _pow2_pad(max(1, G_real))
    group_counts = np.zeros((G, N + 1), np.int32)
    pod_group_member = np.zeros((P, G), bool)
    anchor_node = np.full(G, -1, np.int64)       # node idx of initial anchor
    anchor_unknown = np.zeros(G, bool)           # anchor exists off-list
    if group_ids:
        g_ns = np.array([k[0] for k in group_ids], np.int32)     # [G_real]
        g_si = np.array([k[1] for k in group_ids], np.int64)
        pod_group_member[:, :G_real] = subset_pending[:, g_si] & \
            (pod_ns[:, None] == g_ns[None, :])
        if E:
            e_feat = feat_matrix(ef_ij, E)                      # [E, T]
            e_hits = e_feat @ svc_req.astype(np.float32).T       # [E, S]
            subset_exist = e_hits == req_cnt[None, :]
            member_exist = subset_exist[:, g_si] & \
                (e_ns[:, None] == g_ns[None, :])                 # [E, G_real]
            for g in range(G_real):
                mask = member_exist[:, g]
                if mask.any():
                    group_counts[g, :] = np.bincount(
                        e_host[mask], minlength=N + 1).astype(np.int32)
                    first = int(np.argmax(mask))
                    a = int(e_host[first])
                    if a < N:
                        anchor_node[g] = a
                    else:
                        anchor_unknown[g] = True

    # -- policy: NodeLabelPresence -> node_extra_ok ------------------------
    # cordon folds in first, unconditionally: spec.unschedulable is
    # structural (the serial twin is the always-on Schedulable
    # predicate), not part of the policy vocabulary
    extra_ok = (node_extra_ok.copy() if node_extra_ok is not None
                else np.ones(N, bool))
    for i, n in enumerate(nodes):
        if n.spec.unschedulable:
            extra_ok[i] = False
    if policy.label_presence:
        for i, n in enumerate(nodes):
            lbls = n.metadata.labels or {}
            for labels, presence in policy.label_presence:
                for l in labels:
                    if (l in lbls) != presence:
                        extra_ok[i] = False
                        break

    # -- policy: NodeLabelPriority -> static additive score ----------------
    score_static = np.zeros(N, np.int32)
    if policy.label_prefs:
        for i, n in enumerate(nodes):
            lbls = n.metadata.labels or {}
            acc = 0
            for label, presence, weight in policy.label_prefs:
                if (label in lbls) == presence:
                    acc += 10 * weight
            score_static[i] = acc

    # -- policy: ServiceAffinity value codes + anchors ---------------------
    L = len(policy.affinity_labels)
    node_aff_vals = np.full((N, L), -1, np.int32)
    pod_aff_static = np.full((P, L), -2, np.int32)
    anchor_vals0 = np.full((G, L), -3, np.int32)
    has_anchor0 = np.zeros(G, bool)
    if L:
        val_vocabs: List[Dict[str, int]] = [{} for _ in range(L)]
        for li, label in enumerate(policy.affinity_labels):
            vocab = val_vocabs[li]
            for i, n in enumerate(nodes):
                v = (n.metadata.labels or {}).get(label)
                if v is not None:
                    node_aff_vals[i, li] = intern(vocab, v)
            for j, p in enumerate(pending_pods):
                v = (p.spec.node_selector or {}).get(label)
                if v is not None:
                    pod_aff_static[j, li] = intern(vocab, v)
        has_anchor0[:] = (anchor_node >= 0) | anchor_unknown
        ok = anchor_node >= 0
        anchor_vals0[ok] = node_aff_vals[anchor_node[ok]]
        # serial semantics: a pod consulting an anchor whose host is not a
        # known node fails that pod's schedule() (NodeInfo lookup error,
        # predicates.go:238-324) and the driver requeues it with backoff.
        # Mark exactly those pods infeasible everywhere (an impossible
        # pinned code) so the rest of the wave schedules normally.
        if anchor_unknown.any():
            needs_anchor = (pod_gid >= 0) & (pod_aff_static == -2).any(axis=1)
            for j in np.nonzero(needs_anchor)[0]:
                if anchor_unknown[pod_gid[j]]:
                    pod_aff_static[j, 0] = -100

    # -- policy: ServiceAntiAffinity zone codes ----------------------------
    A = len(policy.anti_affinity)
    node_zone = np.full((A, N), -1, np.int32)
    for a, (label, _w) in enumerate(policy.anti_affinity):
        vocab: Dict[str, int] = {}
        for i, n in enumerate(nodes):
            v = (n.metadata.labels or {}).get(label)
            if v is not None:
                node_zone[a, i] = intern(vocab, v)

    return ClusterSnapshot(
        node_names=[n.metadata.name for n in nodes],
        resource_names=resource_names,
        cap=cap, advertised=advertised,
        fit_used=fit_used, fit_exceeded=fit_exceeded,
        score_used=score_used,
        node_ports=node_ports, node_sel=node_sel, node_pds=node_pds,
        node_extra_ok=extra_ok,
        pod_names=pod_names,
        req=req,
        pod_ports=pod_ports, pod_sel=pod_sel, pod_pds=pod_pds,
        pod_host_idx=pod_host_idx, tie_hi=tie_hi, tie_lo=tie_lo,
        pod_gid=pod_gid, pod_group_member=pod_group_member,
        group_counts=group_counts,
        pod_rid=pod_rid, pod_run_start=pod_run_start,
        score_static=score_static,
        node_aff_vals=node_aff_vals, pod_aff_static=pod_aff_static,
        anchor_vals0=anchor_vals0, has_anchor0=has_anchor0,
        node_zone=node_zone,
        pod_prio=pod_prio, pod_can_preempt=pod_can_preempt,
        band_prio=band_prio, evict_cap=evict_cap, evict_cnt=evict_cnt,
        policy=policy,
        w_least_requested=policy.w_lr, w_spreading=policy.w_spread,
        w_equal=policy.w_equal,
    )
