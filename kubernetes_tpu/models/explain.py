"""kube-explain — batched unschedulability diagnosis from the dense planes.

The scan reports an unschedulable pod as ``chosen == -1`` and the
scheduler events ``FitError(pod, {})`` — an empty predicate map, where
the reference renders a per-predicate failure map through the same event
path (ref: pkg/scheduler/generic_scheduler.go findNodesThatFit ->
FailedPredicateMap -> scheduler.go Eventf). This module closes that gap
for the batched path: for the pods a wave returned unschedulable, it
decomposes the decision against the SAME planes the scan consumed —
per-pod, per-filter node-elimination counts — and renders the k8s-idiom
event line::

    0/10000 nodes available: 9988 Insufficient cpu, 12 Port conflict

**Attribution contract** (the single definition both :func:`explain_wave`
and the serial twin :func:`kubernetes_tpu.models.oracle.explain_serial`
implement; count-identity between them is the proof, exactly like every
other solver feature in this repo):

- a pod's diagnosis is evaluated against the cluster state *its own scan
  step saw*: the wave-start planes plus every EARLIER pod's committed
  placement (unschedulable pods change nothing; preempting placements
  subtract the evicted bands' capacity, and victims conservatively
  RETAIN their ports/PDs — the scan's conservative-retention carry);
- each eliminated node is attributed to exactly ONE reason, the first
  failing filter in the serial scheduler's short-circuit order
  (``find_nodes_that_fit`` over the default provider's predicate list):
  **Port conflict** -> **resources** -> **PD conflict** ->
  **Node selector mismatch** -> **Host mismatch** ->
  **Node label presence** (policy mask, checked last) — so per-pod
  counts are disjoint and sum to the node count;
- within resources, attribution goes to the first insufficient dimension
  in CANONICAL rank order (cpu, memory, then remaining resource names
  lexicographically — rank, not column index, so the full and
  incremental encoders' differing sticky column orders cannot change a
  count), rendered ``Insufficient <resource>``; a greedy-pre-exceeded
  node whose headroom would otherwise fit reports **Node
  overcommitted** (CheckPodsExceedingCapacity semantics: an EXISTING
  pod already didn't fit);
- when the wave shipped preemption bands (B > 0) the pod-level preempt
  state rides along: ``Never`` (preemptionPolicy forbids eviction) vs
  ``no_prefix`` (the pod may preempt, but the scan proved no
  lower-priority victim prefix frees enough anywhere — re-deriving that
  search here would only restate what ``chosen == -1`` already proved).

**Cost discipline**: diagnosis runs strictly off the hot path — only for
unschedulable pods, host-side on the planes the encoder already holds
(the per-dimension gcd scaling the device path applies is
comparison-exact, so the unscaled snapshot planes give identical
verdicts), through a jitted kernel whose pod axis is pow-2 bucketed
(``_EXPLAIN_MAX_BATCH`` cap) so one pending pod does not compile per
distinct count. The :class:`Explainer` adds a token-bucket rate limit; a
declined wave keeps the legacy generic event message and is counted in
``scheduler_explain_skipped_total``. Accepted tradeoff: the FIRST
diagnosed bucket of a shape pays its jit compile inline on the loop
thread — the same per-shape cost every wave-solve bucket already pays
inline, an order of magnitude smaller here (a [Q<=32, N] mask program
vs the sequential-commit scan), and only ever spent on a wave that is
already failing pods.

Unsupported waves (diagnosis declines, never guesses): gang waves (the
checkpoint/rollback carry would need replaying), CheckServiceAffinity
policies (the encoders hand over each group's anchor as the wave began,
but a group's first commit inside the wave sets it for the pods after, and
the host-side replay below carries no anchor), and all-infeasible policies
(no prioritizers: the serial path fails every pod before filters run).
"""

from __future__ import annotations

import functools
import logging
import time
from typing import Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from kubernetes_tpu.models import preempt as preempt_mod
from kubernetes_tpu.models.snapshot import ClusterSnapshot
from kubernetes_tpu.util import metrics

__all__ = ["PodDiagnosis", "ExplainUnsupported", "Explainer",
           "explain_wave", "format_message", "dominant_reason",
           "canonical_rank", "REASON_PORT", "REASON_OVERCOMMIT",
           "REASON_PD", "REASON_SELECTOR", "REASON_HOST", "REASON_LABEL",
           "REASON_UNEXPLAINED", "insufficient_reason"]

# The reason vocabulary (kubectl-visible strings; the record's reason
# histogram keys). Insufficient-<resource> is generated per dimension.
REASON_PORT = "Port conflict"
REASON_OVERCOMMIT = "Node overcommitted"
REASON_PD = "PD conflict"
REASON_SELECTOR = "Node selector mismatch"
REASON_HOST = "Host mismatch"
REASON_LABEL = "Node label presence"
# metrics-only bucket: unschedulable pods whose wave was not explained
# (rate-limited / unsupported / hot-path refusal) — the by-reason counter
# always sums to the pods counter
REASON_UNEXPLAINED = "unexplained"

# preempt-state rendering (PodDiagnosis.preempt -> event suffix)
_PREEMPT_SUFFIX = {
    "Never": "; preemption not attempted (preemptionPolicy: Never)",
    "no_prefix": "; preemption would not help (no lower-priority victim "
                 "set frees enough)",
}

# kernel reason codes (precedence is applied by overwrite order in the
# kernel, NOT by code value): 0 = feasible, fixed codes below, and
# _CODE_RES + canonical-rank for Insufficient-<dim>
_CODE_PORT = 1
_CODE_OVERCOMMIT = 2
_CODE_PD = 3
_CODE_SELECTOR = 4
_CODE_HOST = 5
_CODE_LABEL = 6
_CODE_RES = 8

# pod-axis jit bucket lid: one compile per pow-2 bucket up to this, so a
# storm wave chunks instead of compiling at its exact unschedulable count
_EXPLAIN_MAX_BATCH = 32


def insufficient_reason(resource: str) -> str:
    return f"Insufficient {resource}"


_log = logging.getLogger("kubernetes_tpu.models.explain")


class ExplainUnsupported(Exception):
    """The wave's configuration is outside the diagnosis vocabulary;
    callers fall back to the generic FitError message."""


class PodDiagnosis(NamedTuple):
    """One unschedulable pod's decomposition: disjoint per-reason node
    counts (summing to ``n_nodes``) plus the preempt state (empty when
    the wave carried no bands)."""

    n_nodes: int
    counts: Dict[str, int]
    preempt: str = ""       # "" | "Never" | "no_prefix"


def canonical_rank(resource_names: Sequence[str]) -> np.ndarray:
    """[R] canonical attribution rank per snapshot column: cpu 0, memory
    1, everything else by name — column order (which differs between the
    full and incremental encoders' sticky vocabularies) can never change
    which dimension a node's elimination is attributed to."""
    rest = sorted(n for n in resource_names[2:])
    order = {name: 2 + k for k, name in enumerate(rest)}
    return np.array([0 if r == 0 else 1 if r == 1
                     else order[name]
                     for r, name in enumerate(resource_names)], np.int32)


def format_message(diag: PodDiagnosis, top_k: int = 4) -> str:
    """The k8s-idiom FailedScheduling line: ``0/N nodes available:``
    plus the top-k reasons by count (ties broken by reason name for a
    deterministic, goldens-testable render), a summed ``other`` bucket
    for the tail, and the preempt-state suffix."""
    items = sorted(diag.counts.items(), key=lambda kv: (-kv[1], kv[0]))
    msg = f"0/{diag.n_nodes} nodes available"
    if items:
        parts = [f"{n} {reason}" for reason, n in items[:top_k]]
        rest = sum(n for _, n in items[top_k:])
        if rest:
            parts.append(f"{rest} other")
        msg += ": " + ", ".join(parts)
    return msg + _PREEMPT_SUFFIX.get(diag.preempt, "")


def dominant_reason(diag: PodDiagnosis) -> str:
    """The reason eliminating the most nodes (ties by name) — the
    ``scheduler_unschedulable_total{reason=...}`` bucket this pod lands
    in."""
    if not diag.counts:
        return REASON_UNEXPLAINED
    return min(diag.counts.items(), key=lambda kv: (-kv[1], kv[0]))[0]


@functools.partial(jax.jit, static_argnames=("pol",))
def _diag_kernel(cap, fit_used, fit_exceeded, node_ports, node_pds,
                 node_sel, extra_ok, rank, req, p_ports, p_pds, p_sel,
                 p_host, pol):
    """One diagnosis batch: [Q] pod rows against one carry state ->
    per-pod per-reason node counts [Q, 8 + R]. Compiled per (shapes,
    policy) like every other solver program; the pod axis arrives pow-2
    bucketed so the compile set stays bounded under churn."""
    N, R = cap.shape
    Q = req.shape[0]
    arange_n = jnp.arange(N, dtype=jnp.int32)
    code = jnp.zeros((Q, N), jnp.int32)

    # lowest-precedence first; each later filter overwrites, so the final
    # code per node is the FIRST failing filter in serial short-circuit
    # order (ports, resources, disk, selector, host, label-presence)
    code = jnp.where(~extra_ok[None, :], jnp.int32(_CODE_LABEL), code)
    if pol.use_host:
        host_ok = (p_host[:, None] == -1) | \
                  (p_host[:, None] == arange_n[None, :])
        code = jnp.where(~host_ok, jnp.int32(_CODE_HOST), code)
    if pol.use_selector:
        # same exact boolean matmul as the scan's Filter pre-pass
        viol = jnp.dot(p_sel.astype(jnp.float32),
                       (~node_sel).astype(jnp.float32).T,
                       precision=jax.lax.Precision.HIGHEST)
        code = jnp.where(viol != 0, jnp.int32(_CODE_SELECTOR), code)
    if pol.use_disk:
        dconf = jnp.dot(p_pds.astype(jnp.float32),
                        node_pds.astype(jnp.float32).T,
                        precision=jax.lax.Precision.HIGHEST)
        code = jnp.where(dconf != 0, jnp.int32(_CODE_PD), code)
    if pol.use_resources:
        unconstrained = (cap == 0) & (jnp.arange(R) < 2)[None, :]
        insuf = ~(unconstrained[None, :, :] |
                  ((cap - fit_used)[None, :, :] >= req[:, None, :]))
        any_insuf = insuf.any(axis=2)
        first_rank = jnp.min(
            jnp.where(insuf, rank[None, None, :], jnp.int32(2**30)),
            axis=2)                                          # [Q, N]
        zero_req = jnp.all(req == 0, axis=1)                 # [Q]
        res_fail = (~zero_req[:, None]) & \
            (fit_exceeded[None, :] | any_insuf)
        res_code = jnp.where(any_insuf, jnp.int32(_CODE_RES) + first_rank,
                             jnp.int32(_CODE_OVERCOMMIT))
        code = jnp.where(res_fail, res_code, code)
    if pol.use_ports:
        pconf = jnp.dot(p_ports.astype(jnp.float32),
                        node_ports.astype(jnp.float32).T,
                        precision=jax.lax.Precision.HIGHEST)
        code = jnp.where(pconf != 0, jnp.int32(_CODE_PORT), code)

    C = _CODE_RES + R
    counts = jnp.sum(code[:, :, None] ==
                     jnp.arange(C, dtype=jnp.int32)[None, None, :],
                     axis=1, dtype=jnp.int32)                # [Q, C]
    return counts


def _pow2(n: int, minimum: int = 8) -> int:
    out = minimum
    while out < n:
        out *= 2
    return out


def explain_wave(snap: ClusterSnapshot, chosen, scores
                 ) -> Dict[int, PodDiagnosis]:
    """Diagnose every unschedulable pod of one solved wave.

    ``chosen``/``scores`` are the raw solve outputs (wave row order;
    pod-axis padding rows are ignored). Returns {row: PodDiagnosis} for
    rows with ``chosen < 0``. The carry is replayed host-side: walking
    the wave in order, unschedulable runs are diagnosed in one kernel
    batch against the current planes, then each placed pod's commit
    (including preemption's freed capacity) is applied — so every pod is
    judged against exactly the state its own scan step saw.

    Raises :class:`ExplainUnsupported` for gang waves, affinity
    policies, and all-infeasible policies (see module docstring).
    """
    pol = snap.policy
    if pol.has_affinity:
        raise ExplainUnsupported(
            "CheckServiceAffinity anchors set inside the wave are not "
            "replayed")
    if pol.all_infeasible:
        raise ExplainUnsupported(
            "no prioritizers configured: every pod fails before filters")
    if snap.has_gangs:
        raise ExplainUnsupported(
            "gang waves roll back through the checkpoint carry")

    P = len(snap.pod_names)
    chosen = np.asarray(chosen)[:P]
    scores = np.asarray(scores)[:P]
    unsched = np.nonzero(chosen < 0)[0]
    if unsched.size == 0:
        return {}
    N = snap.n_nodes
    if N == 0:
        # the serial scheduler fails the whole wave before any predicate
        # runs (schedule() raises on an empty minion list)
        return {int(j): PodDiagnosis(0, {}) for j in unsched}

    from kubernetes_tpu.models.batch_solver import ensure_x64
    ensure_x64()

    R = snap.cap.shape[1]
    rank = canonical_rank(snap.resource_names)
    rank_to_name = {int(rank[r]): name
                    for r, name in enumerate(snap.resource_names)}
    band_prio = snap.band_prio if snap.band_prio is not None \
        else np.zeros(0, np.int32)
    B = len(band_prio)

    # mutable carry replay state (wave-start planes, copied)
    fit_used = snap.fit_used.copy()
    ports = snap.node_ports.copy()
    pds = snap.node_pds.copy()
    evict_cap = snap.evict_cap.copy() if B else None

    can_p = snap.pod_can_preempt if snap.pod_can_preempt is not None \
        else np.ones(P, bool)

    out: Dict[int, PodDiagnosis] = {}

    def flush(batch: List[int]) -> None:
        for lo in range(0, len(batch), _EXPLAIN_MAX_BATCH):
            rows = batch[lo:lo + _EXPLAIN_MAX_BATCH]
            Q = _pow2(len(rows))
            sel = np.zeros(Q, np.int64)
            sel[:len(rows)] = rows
            counts = np.asarray(_diag_kernel(
                snap.cap, fit_used, snap.fit_exceeded, ports, pds,
                snap.node_sel, snap.node_extra_ok, rank,
                snap.req[sel], snap.pod_ports[sel], snap.pod_pds[sel],
                snap.pod_sel[sel], snap.pod_host_idx[sel], pol))
            for k, j in enumerate(rows):
                row = counts[k]
                d: Dict[str, int] = {}
                for code, name in ((_CODE_PORT, REASON_PORT),
                                   (_CODE_OVERCOMMIT, REASON_OVERCOMMIT),
                                   (_CODE_PD, REASON_PD),
                                   (_CODE_SELECTOR, REASON_SELECTOR),
                                   (_CODE_HOST, REASON_HOST),
                                   (_CODE_LABEL, REASON_LABEL)):
                    if row[code]:
                        d[name] = int(row[code])
                for r in range(R):
                    c = row[_CODE_RES + r]
                    if c:
                        d[insufficient_reason(rank_to_name[r])] = int(c)
                pstate = ""
                if B:
                    # the scan already searched every (node, threshold)
                    # prefix and found none — re-deriving it would only
                    # restate chosen == -1 (module docstring)
                    pstate = "no_prefix" if can_p[j] else "Never"
                out[int(j)] = PodDiagnosis(N, d, pstate)

    batch: List[int] = []
    for j in range(P):
        c = int(chosen[j])
        if c < 0:
            batch.append(j)
            continue
        if batch:
            flush(batch)
            batch = []
        s = int(scores[j])
        if B and preempt_mod.is_preempt_score(s):
            # preempting commit: evicted bands leave both the fit
            # accumulator and the evictable planes; ports/PDs of victims
            # are conservatively retained (the scan's carry rule)
            ceiling = int(band_prio[preempt_mod.ceiling_slot(s)])
            emask = band_prio <= ceiling
            freed = evict_cap[c][emask].sum(axis=0)
            fit_used[c] += snap.req[j] - freed
            evict_cap[c][emask] = 0
        else:
            fit_used[c] += snap.req[j]
        ports[c] |= snap.pod_ports[j]
        pds[c] |= snap.pod_pds[j]
    if batch:
        flush(batch)
    return out


class Explainer:
    """The live scheduler's diagnosis gate: rate limit + metrics around
    :func:`explain_wave`, called on the wave loop's thread.

    A token bucket caps invocations (unschedulable pods requeue and
    re-diagnose every wave in a full cluster; the events compress
    client-side but the diagnosis work would not). Declined waves fall
    back to the generic FitError message and are counted by reason in
    ``scheduler_explain_skipped_total``; every unschedulable pod counts
    in ``scheduler_unschedulable_pods_total`` and exactly one
    ``scheduler_unschedulable_total{reason=...}`` bucket regardless
    (``unexplained`` when diagnosis was skipped), so the by-reason
    family always sums to the pods family.
    """

    def __init__(self, qps: float = 2.0, burst: int = 4, top_k: int = 4):
        self._qps = qps
        self._burst = float(burst)
        self._tokens = float(burst)
        self._last = time.monotonic()
        self.top_k = top_k
        self._mx = metrics.explain_metrics()

    def _admit(self) -> bool:
        if self._qps <= 0:
            return True
        now = time.monotonic()
        self._tokens = min(self._burst,
                           self._tokens + (now - self._last) * self._qps)
        self._last = now
        if self._tokens < 1.0:
            return False
        self._tokens -= 1.0
        return True

    def _skip(self, reason: str, n_pods: int) -> Dict[int, str]:
        self._mx.skipped.inc(reason)
        self._mx.reasons.inc(REASON_UNEXPLAINED, by=n_pods)
        return {}

    def diagnose_wave(self, snap: ClusterSnapshot, chosen, scores,
                      n_unsched: Optional[int] = None) -> Dict[int, str]:
        """-> {wave row: FailedScheduling message} for unschedulable
        rows (empty when diagnosis was declined).

        ``n_unsched`` is the caller's count of pods it is about to fail
        — it can EXCEED count(chosen < 0) (the full-encoder path
        requeues preempt-scored rows by forcing their host to None
        while chosen stays >= 0); those extra rows are counted in the
        pods family and land in the ``unexplained`` bucket, keeping the
        sums-to-pods invariant. None derives the count from ``chosen``.
        """
        P = len(snap.pod_names)
        n_rows = int(np.count_nonzero(np.asarray(chosen)[:P] < 0))
        n = n_rows if n_unsched is None else max(int(n_unsched), n_rows)
        if n == 0:
            return {}
        self._mx.pods.inc(by=n)
        if not self._admit():
            return self._skip("rate_limited", n)
        t0 = time.thread_time()
        try:
            diags = explain_wave(snap, chosen, scores)
        except ExplainUnsupported:
            return self._skip("unsupported", n)
        except Exception:
            # the pods counter already advanced: the skip bucket must
            # too, or the by-reason family stops summing to it forever
            _log.exception("kube-explain diagnosis failed")
            return self._skip("error", n)
        self._mx.invocations.inc()
        self._mx.seconds.inc(by=max(0.0, time.thread_time() - t0))
        out = {}
        for row, diag in diags.items():
            self._mx.reasons.inc(dominant_reason(diag))
            out[row] = format_message(diag, top_k=self.top_k)
        if n > len(out):
            # rows failed by the caller without a chosen == -1 verdict
            # (the forced-requeue class above): disclosed, not dropped
            self._mx.reasons.inc(REASON_UNEXPLAINED, by=n - len(out))
        return out
