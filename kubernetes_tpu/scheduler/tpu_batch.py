"""The "tpu-batch" scheduler profile — wave scheduling on the batch solver.

Replaces the reference's one-pod-at-a-time loop
(plugin/pkg/scheduler/scheduler.go:87-90 ``util.Forever(scheduleOne)``) with:

    drain a wave from the FIFO -> snapshot cluster state -> ONE TPU solve
    -> commit bindings sequentially -> assume pods

Decisions are bit-identical to running the serial scheduler over the same
wave (models/oracle.py contract), because the solver reproduces the serial
sequential-commit semantics inside one compiled call. The Binding write path,
backoff/error handling, and the assume/confirm modeler are shared with the
serial driver — this is a drop-in Config.algorithm-level swap, the same
boundary the reference exposes for alternate schedulers.

Bind conflicts (another scheduler won the CAS) invalidate that pod only; the
error handler requeues it and the next wave re-solves against fresh state.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from collections import OrderedDict
from datetime import timezone
from typing import List, NamedTuple, Optional

from kubernetes_tpu.api import types as api
from kubernetes_tpu.models import explain as explain_mod
from kubernetes_tpu.models import gang
from kubernetes_tpu.models import preempt as preempt_mod
from kubernetes_tpu.models.batch_solver import (decisions_to_names,
                                                peer_bound_of,
                                                snapshot_to_host_inputs,
                                                solve, warm_compile,
                                                wave_parts)
from kubernetes_tpu.models.incremental import (GROUP_FLOOR,
                                               IncrementalEncoder)
from kubernetes_tpu.models.policy import BatchPolicy, batch_policy_from
from kubernetes_tpu.models.resident import ResidentPlanes
from kubernetes_tpu.models.snapshot import encode_snapshot
from kubernetes_tpu.runtime.clone import deep_clone
from kubernetes_tpu.scheduler.driver import ConfigFactory, SchedulerConfig
from kubernetes_tpu.scheduler.generic import FitError
from kubernetes_tpu.util import metrics, tracing

__all__ = ["BatchScheduler"]

_log = logging.getLogger("kubernetes_tpu.scheduler.tpu_batch")

# KTPU_DEBUG gates the journal-replay bit-identity check (same idiom as
# models/incremental._DEBUG_VERIFY_EVICT): after every replay resync the
# from-scratch diff-walk re-runs and the resident fingerprint must not
# move. Assumes a quiescent store between replay and walk (tests, debug
# runs).
_DEBUG_REPLAY = os.environ.get("KTPU_DEBUG", "") not in ("", "0")


class _WaveMetrics:
    """Per-wave instrumentation (the kubelet-metrics analog for the wave
    loop, ref: pkg/kubelet/metrics/metrics.go — instrumented, no targets).
    Scraped via the scheduler binary's --metrics-port; the churn harness
    reads encode quantiles from here (the MapPodsToMachines
    rebuild-per-cycle cost being designed away, ref:
    pkg/scheduler/predicates.go:354-375)."""

    _singleton = None

    def __init__(self):
        reg = metrics.default_registry()
        buckets = (0.001, 0.0025, 0.005, 0.01, 0.02, 0.05, 0.1, 0.25,
                   0.5, 1.0, 2.5)
        self.encode = reg.histogram(
            "scheduler_wave_encode_seconds",
            "Snapshot encode time per wave", buckets=buckets)
        self.solve = reg.histogram(
            "scheduler_wave_solve_seconds",
            "Solver time per wave", buckets=buckets)
        self.commit = reg.histogram(
            "scheduler_wave_commit_seconds",
            "Bind + assume time per wave (the store round-trips)",
            buckets=buckets)
        # the parts of the phases above and the phases with no histogram
        # of their own (drain.wait, solve.ship, commit.bind, ...)
        self.part = wave_parts()
        self.pods = reg.counter(
            "scheduler_wave_pods_total", "Pods drained into waves")
        self.cut = reg.counter(
            "scheduler_wave_cut_total",
            "Why a wave's drain ended: full (wave_size reached), linger "
            "(the deadline passed between two pops: pods were still "
            "coming), empty (a pop ran into the deadline: the queue was "
            "dry)", label_names=("reason",))
        self.queue_left = reg.counter(
            "scheduler_wave_queue_left_total",
            "Pods still in the FIFO when a wave's drain ended, summed")
        self.group_cuts = reg.counter(
            "scheduler_wave_group_cuts_total",
            "Waves cut short because their pods named more distinct "
            "service groups than the kernel takes rows; the pods cut off "
            "head the next wave")
        self.group_cap_rows = reg.counter(
            "scheduler_wave_group_cap_rows_total",
            "The most group rows the kernel takes (the cap a wave is cut "
            "at, IncrementalEncoder.group_cap), added once a wave")
        self.resyncs = reg.counter(
            "scheduler_wave_encode_resyncs_total",
            "Full-list encoder syncs (vs O(changed) delta waves)")
        self.bind_fallback = reg.counter(
            "scheduler_bind_fallback_total",
            "Waves committed via per-pod binder.bind because the binder "
            "lacks the bind_many seam (a mis-wired live stack pays one "
            "HTTP round-trip per pod)")
        # kube-slipstream: a reintroduced recompile/re-encode cliff is a
        # few multi-second waves in a sea of fast ones — quantiles average
        # it away, the running max cannot (perfgate advisory key)
        self.stall_max = reg.gauge(
            "scheduler_wave_stall_max_seconds",
            "Largest single-wave encode or solve stall since boot")
        self._stall_lock = threading.Lock()
        self._stall_max_v = 0.0

    def note_stall(self, dt: float) -> None:
        with self._stall_lock:
            if dt > self._stall_max_v:
                self._stall_max_v = dt
                self.stall_max.set(dt)


def _wave_metrics() -> _WaveMetrics:
    if _WaveMetrics._singleton is None:
        _WaveMetrics._singleton = _WaveMetrics()
    return _WaveMetrics._singleton


class _WaveDecisions(NamedTuple):
    """One wave's solve outcome: per-pod host names (None =
    unschedulable) plus, for pods the solver placed VIA PREEMPTION
    (kube-preempt), the concrete victim sets the commit must evict
    atomically with the bind. ``t0`` is the solve-dispatch instant, the
    start of the preempt-to-bind latency window.

    ``snap``/``chosen``/``scores`` carry the solved wave's inputs and
    raw outputs to the commit so kube-explain (models/explain.py) can
    decompose any unschedulable rows against the planes the scan
    consumed — references only, nothing is copied, and they die with
    the wave."""

    hosts: list
    victims: list           # aligned; None = normal placement
    t0: float = 0.0
    snap: object = None     # ClusterSnapshot the solve consumed
    chosen: object = None   # raw [P] node indices (-1 = unschedulable)
    scores: object = None   # raw [P] score channel (preempt encoding)


class BatchScheduler:
    """Wave-based driver over SchedulerConfig plumbing.

    ``batch_policy`` is the normalized form of the configured provider /
    policy file (models/policy.batch_policy_from); the solver honors the
    same predicate/priority sets and weights the serial driver would use.
    When not given explicitly it is derived from the config's recorded
    provider/policy, so constructing this class for an unsupported
    configuration raises UnsupportedPolicy — a non-default policy can never
    silently fall through to default-provider decisions."""

    def __init__(self, config: SchedulerConfig, factory: ConfigFactory,
                 client, wave_size: int = 1024, wave_linger_s: float = 0.02,
                 solve_fn=None, batch_policy: BatchPolicy = None,
                 solver=None):
        self.config = config
        self.factory = factory
        self.client = client
        self.wave_size = wave_size
        self.wave_linger_s = wave_linger_s
        # flag, not identity: `self._default_solve` creates a fresh bound
        # method on every attribute access, so `is` can never match it
        self._using_default_solve = solve_fn is None
        self.solve_fn = solve_fn or self._default_solve
        self.batch_policy = batch_policy or batch_policy_from(
            getattr(config, "provider", None), getattr(config, "policy", None))
        # shared-solver seam: an explicit RemoteSolver, or one built from
        # the config's recorded solver topology (cmd/scheduler
        # --solver-addr). None = solve in-process, the reference shape.
        addr = getattr(config, "solver_addr", "")
        if solver is None and addr:
            from kubernetes_tpu.solver.client import RemoteSolver
            solver = RemoteSolver(
                addr,
                fallback=getattr(config, "solver_fallback",
                                 "inprocess") != "requeue")
        self.solver = solver
        # in-process device-mesh solve (kube-scheduler --mesh): resolved
        # once — None when single-device or off. Waves above the node
        # floor then take parallel.mesh.solve_sharded (its measured
        # kernel-vs-mesh crossover included); bit-identical either way.
        from kubernetes_tpu.parallel.mesh import maybe_mesh
        self._mesh = maybe_mesh(getattr(config, "mesh", "auto"),
                                getattr(config, "pods_axis", 1))
        if self.solver is not None and self._mesh is not None:
            # a daemon wave solves under the daemon's own --mesh; this
            # covers the in-process fallback when the daemon is away
            self.solver.fallback_mesh = self._mesh
        # delta-maintained node planes + sticky vocabularies: per-wave
        # encode cost is O(changed pods), and pow-2 bucketing keeps the
        # compiled-shape count bounded under churn — under every policy the
        # batch solver models, CheckServiceAffinity's anchors included
        self._encoder = IncrementalEncoder(self.batch_policy)
        # modeler changelog cursor for the O(changed) wave path; None
        # until the first full sync establishes the resident planes
        self._delta_token = None
        # kube-slipstream journal-replay resync: a cadence-gated
        # copy-on-write checkpoint of the encoder planes, paired with the
        # modeler token it is causal with. A resync restores the
        # checkpoint and replays the changelog (O(missed events)) instead
        # of re-encoding the cluster; `checkpoint_every` keeps the gap
        # far inside the store changelog window (client/cache.Store
        # _LOG_MAX events vs ~3 events/pod per wave).
        self._sx = metrics.slipstream_metrics()
        self._ckpt = None            # (encoder state, modeler token)
        self._ckpt_waves = 0
        self.checkpoint_every = 4
        # kube-slipstream prewarm (solver/prewarm.py): in-process solve
        # topologies compile the next shape bucket off the wave loop; a
        # remote-solver worker has no local programs to warm (the daemon
        # runs its own controller)
        self._prewarm = None
        self._prewarm_snap = None
        # the node planes the in-process solve keeps between waves, on the
        # host and on the device(s) (models/resident.py): this scheduler's
        # own; the prewarm thread compiles on planes of its own
        self._resident = ResidentPlanes()
        if self.solver is None and self._using_default_solve and \
                os.environ.get("KTPU_PREWARM", "auto") != "off":
            from kubernetes_tpu.solver.prewarm import PrewarmController
            self._prewarm = PrewarmController(self._prewarm_compile,
                                              name="sched-prewarm")
        # kube-explain: rate-limited unschedulability diagnosis over the
        # solved wave's planes (models/explain.py); only consulted when a
        # wave returns unschedulable pods, so a wave where every pod
        # binds never pays for it
        self._explainer = explain_mod.Explainer()
        self._stop = threading.Event()
        # pod-lifecycle latency (always-on metrics; the kube-trace span
        # layer is the opt-in causal complement): bind instants by uid,
        # consumed when the assigned-pods reflector delivers the bound pod
        # back through the scheduler's own watch stream. Bounded — a pod
        # whose confirm never arrives must not leak the map.
        self._pod_lat = metrics.pod_latency_metrics()
        # the wait for a wave's first pod, carried over the loop's empty
        # ticks: (tracing.clocks() at its start, the wave's trace context);
        # and the context handed from _drain_wave to the wave it drained
        self._wait = None
        self._drain_tctx = None
        # the pods a wave was cut short of (_cut_at_group_cap): they head
        # the next wave, in the order they were drained
        self._carry: List[api.Pod] = []
        self._bind_t: "OrderedDict[str, float]" = OrderedDict()
        # deliveries that beat the arming loop: the batch bind commits
        # server-side before bind_many returns, so the reflector can
        # deliver a bound pod while the commit loop is still arming —
        # the observer stashes the instant here and the arming loop
        # consumes it (losing the race must not lose the sample)
        self._obs_t: "OrderedDict[str, float]" = OrderedDict()
        self._bind_t_lock = threading.Lock()
        store = getattr(factory, "scheduled_pods", None)
        if store is not None and hasattr(store, "subscribe"):
            store.subscribe(self._observe_scheduled)

    _BIND_T_MAX = 1 << 16

    def _observe_scheduled(self, pod) -> None:
        """Store.subscribe hook (reflector delivery thread): the bound
        pod came back through the watch — the fan-out leg of its path."""
        try:
            uid = pod.metadata.uid
        except AttributeError:
            return
        now = time.monotonic()
        with self._bind_t_lock:
            t0 = self._bind_t.pop(uid, None)
            if t0 is None:
                # not armed (yet): either a re-delivery of an already-
                # observed pod, a foreign scheduler's bind, or a delivery
                # that RACED ahead of this scheduler's own arming loop.
                # Stash the instant; the arming loop consumes it so the
                # fastest deliveries are recorded (~0 s), not dropped.
                self._obs_t[uid] = now
                while len(self._obs_t) > self._BIND_T_MAX:
                    self._obs_t.popitem(last=False)
                return
        self._pod_lat.watch_observe.observe(now - t0)

    # -- wave assembly ------------------------------------------------------
    def _drain_wave(self, timeout: Optional[float]) -> List[api.Pod]:
        wm = _wave_metrics()
        if self._wait is None:
            self._wait = (tracing.clocks(), tracing.new_ctx())
        since, tctx = self._wait
        with tracing.phase("wave.drain.wait", wm.part, "drain.wait",
                           parent=tctx, since=since) as ph:
            pods: List[api.Pod] = self._carry
            self._carry = []
            try:
                if not pods:
                    pods = [self.config.next_pod(timeout)]
            except TimeoutError:
                # an empty tick is no wave: its wait belongs to the wave
                # that follows (self._wait stays)
                ph.cancel()
                raise
        self._wait = None
        self._drain_tctx = tctx
        with tracing.phase("wave.drain.collect", wm.part, "drain.collect",
                           parent=tctx) as ph:
            deadline = time.monotonic() + self.wave_linger_s
            cut = "full"
            while len(pods) < self.wave_size:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    cut = "linger"
                    break
                try:
                    pods.append(self.config.next_pod(remaining))
                except TimeoutError:
                    cut = "empty"
                    break
            ph.set(pods=len(pods), cut=cut)
        wm.cut.inc(cut)
        queue = getattr(self.factory, "pod_queue", None)
        if queue is not None:
            wm.queue_left.inc(by=len(queue))
        return pods

    def _wave_ctx(self, pods):
        """The trace context of the wave just drained: the one _drain_wave
        hung its own spans on, or a fresh one where _drain_wave was
        replaced on the instance. None for an idle tick or tracing off."""
        tctx, self._drain_tctx = self._drain_tctx, None
        if not pods:
            return None
        return tctx if tctx is not None else tracing.new_ctx()

    def _make_get_existing(self):
        """Lazy memoized existing-pod list: materialized only when
        something needs it (gang quorum, encoder resync), so the
        steady-state delta path stays O(changed), not O(cluster). The
        token is taken BEFORE the list it pairs with, so an event racing
        the list is re-delivered by the next delta (idempotent in the
        encoder) rather than lost."""
        c = self.config
        memo: dict = {}

        def get_existing():
            if "list" not in memo:
                if hasattr(c.modeler, "token"):
                    memo["token"] = c.modeler.token()
                memo["list"] = c.modeler.list()
            return memo["list"]

        get_existing.pre_token = lambda: memo.get("token")
        return get_existing

    def _prepare_wave(self, pods: List[api.Pod]):
        """Admission for a drained wave: node/service listing + gang
        quorum gate + gang-contiguous ordering. Returns (pending, nodes,
        services, get_existing), or None when the wave emptied (every pod
        was evented + handed to the error handler)."""
        c = self.config
        get_existing = self._make_get_existing()
        try:
            nodes = c.minion_lister.list().items
            services = self.factory.service_store.list()
            pods = self._cut_at_group_cap(pods, services, len(nodes))
            pending, starved = self._gate_gang_quorum(pods, get_existing)
        except Exception as e:
            for pod in pods:
                self._record(pod, "FailedScheduling",
                             "Error scheduling wave: %s", e)
                c.error(pod, e)
            return None
        for pod in starved:
            err = FitError(pod, {})
            self._record(pod, "FailedScheduling",
                         "Pod group below min-members quorum")
            c.error(pod, err)
        if not pending:
            return None
        return gang.order_wave(pending), nodes, services, get_existing

    def _cut_at_group_cap(self, pods: List[api.Pod], services,
                          n_nodes: int) -> List[api.Pod]:
        """One rule for every deployment: a wave holds no more distinct
        service groups than the kernel takes group rows
        (``IncrementalEncoder.group_cut``). The pods cut off head the next
        wave. A wave with a PodGroup is left whole (its members must meet
        the quorum gate together); past the cap it takes the scan."""
        cap = self._encoder.group_cap(n_nodes)
        _wave_metrics().group_cap_rows.inc(by=cap)
        keep = self._encoder.group_cut(pods, services, cap)
        if keep >= len(pods) or \
                any(gang.gang_key(p) is not None for p in pods):
            return pods
        _wave_metrics().group_cuts.inc()
        self._carry = pods[keep:]
        return pods[:keep]

    # -- solving ------------------------------------------------------------
    def _encode_wave(self, nodes, pending, services, get_existing,
                     tctx=None):
        wm = _wave_metrics()
        with tracing.phase("wave.encode", wm.encode, parent=tctx,
                           pods=len(pending)) as ph:
            snap = self._encode_incremental(nodes, pending, services,
                                            get_existing)
        wm.note_stall(ph.wall_s)
        return snap

    def _solve_snap(self, snap, n_pending: int, tctx=None):
        """One wave's solve (in-process or via the shared daemon) ->
        _WaveDecisions, on the loop's one thread. Both paths include the
        gang all-or-nothing post-pass and RemoteSolver falls back
        in-process when the daemon is absent/busy. ``tctx`` is the wave's
        trace; the span's ambient context is what RemoteSolver ships on
        the v3 frame so solverd's spans join this trace.

        kube-preempt: a placed pod whose returned score encodes a
        preemption threshold (models/preempt.py score channel) gets its
        victim set materialized here from the incremental encoder's
        per-node registry — the deterministic replay the oracle gate
        pins. The registry is the one this wave was encoded from: the
        encoder is next written by the following wave's encode."""
        wm = _wave_metrics()
        t0 = time.perf_counter()
        with tracing.phase("wave.solve", wm.solve, parent=tctx,
                           pods=n_pending) as ph:
            if self.solver is not None:
                chosen, scores = self.solver.solve(snap)
            elif self._prewarm is not None:
                # the host-side encode is hoisted out of solve() so the
                # prewarm fill trigger can read this wave's bucket at
                # zero extra cost (solve() needs the host inputs anyway);
                # the snap reference is the exemplar the prewarm thread
                # pads to the queued target bucket
                with tracing.phase("wave.solve.hostprep", wm.part,
                                   "solve.hostprep"):
                    host = self._resident.host_inputs(snap)
                    self._prewarm_snap = snap
                    actual = {"P": n_pending}
                    actual.update(self._encoder.fill_dims())
                    from kubernetes_tpu.solver.service import _dims_of
                    self._prewarm.observe(actual, _dims_of(host))
                chosen, scores = solve(snap, host=host, mesh=self._mesh,
                                       resident=self._resident)
            else:
                chosen, scores = solve(snap, mesh=self._mesh,
                                       resident=self._resident)
        wm.note_stall(ph.wall_s)
        wm.pods.inc(by=n_pending)
        with tracing.phase("wave.names", wm.part, "names", parent=tctx):
            hosts = decisions_to_names(snap, chosen)
            victims = [None] * len(hosts)
            if any(preempt_mod.is_preempt_score(int(s))
                   for s in scores[:len(hosts)]):
                victims = preempt_mod.assign_victims(
                    chosen, scores, snap.band_prio, n_pods=len(hosts),
                    node_pods=self._encoder.resident_on)
        return _WaveDecisions(hosts, victims, t0, snap, chosen, scores)

    def _default_solve(self, nodes, existing, pending, services, tctx=None):
        get_existing = existing if callable(existing) else lambda: existing
        snap = self._encode_wave(nodes, pending, services, get_existing,
                                 tctx=tctx)
        return self._solve_snap(snap, len(pending), tctx=tctx)

    def _encode_incremental(self, nodes, pending, services, get_existing):
        """O(changed + pending) when the modeler's changelog covers the
        gap from the encoder's own token; otherwise kube-slipstream
        journal replay — restore the last checkpoint and replay the
        changelog over it, O(missed events) — and only when the journal
        cannot cover the gap either (no checkpoint yet, window exceeded,
        node/service planes changed) the full O(cluster) list sync, with
        the fallback counted by reason (encoder_resync_full_total).
        The resync token is always taken BEFORE the list it pairs with
        (get_existing records its own pre-token at materialization) so an
        event racing the list is re-delivered rather than lost
        (re-applying an upsert or remove is a no-op in the encoder)."""
        modeler = self.config.modeler
        can_replay = hasattr(modeler, "delta") and hasattr(modeler, "token")
        if self._delta_token is not None and hasattr(modeler, "delta"):
            d = modeler.delta(self._delta_token)
            if d is not None:
                upserted, removed, token = d
                snap = self._encoder.encode_delta(nodes, upserted, removed,
                                                  pending, services)
                if snap is not None:
                    self._delta_token = token
                    self._maybe_checkpoint(token)
                    return snap
        reason = "no_changelog"
        if can_replay:
            snap, reason = self._replay_resync(nodes, pending, services,
                                               get_existing)
            if snap is not None:
                return snap
        if hasattr(modeler, "token"):
            fallback_token = modeler.token()
            existing = get_existing()
            pre = getattr(get_existing, "pre_token", lambda: None)()
            self._delta_token = pre if pre is not None else fallback_token
            _wave_metrics().resyncs.inc()
        else:
            existing = get_existing()
        self._sx.resync_full.inc(reason)
        snap = self._encoder.encode(nodes, existing, pending, services)
        if self._delta_token is not None:
            self._maybe_checkpoint(self._delta_token)
        return snap

    def _maybe_checkpoint(self, token) -> None:
        """Cadence-gated encoder checkpoint at a clean, token-paired
        state (delta success or post-full-sync). Every
        ``checkpoint_every`` waves keeps the replay gap a few thousand
        events deep — far inside the store changelog window — while the
        copy-on-write snapshot stays a per-wave rounding error on the
        loop thread."""
        self._ckpt_waves += 1
        if self._ckpt is not None and \
                self._ckpt_waves < self.checkpoint_every:
            return
        t0 = time.perf_counter()
        try:
            state = self._encoder.checkpoint()
        except ValueError:
            return  # nothing resident yet
        self._sx.checkpoint_s.observe(time.perf_counter() - t0)
        self._ckpt = (state, token)
        self._ckpt_waves = 0

    def _replay_resync(self, nodes, pending, services, get_existing):
        """The journal-replay resync: restore the last checkpoint, then
        replay every store event since its token (the striped store's
        per-shard history ring is the journal backing modeler.delta) —
        O(missed events), not O(cluster). Returns ``(snap, reason)``;
        snap is None when the journal could not cover the gap and the
        caller pays the full re-encode, counted under ``reason``."""
        if self._ckpt is None:
            return None, "no_checkpoint"
        state, ckpt_token = self._ckpt
        d = self.config.modeler.delta(ckpt_token)
        if d is None:
            return None, "window_exceeded"
        upserted, removed, token = d
        self._encoder.restore(state)
        snap = self._encoder.encode_delta(nodes, upserted, removed,
                                          pending, services)
        if snap is None:
            # node/service planes changed (or capacity overflow): the
            # full diff-walk below re-establishes everything; the
            # restored-but-stale planes are simply its starting point
            return None, "planes_changed"
        self._delta_token = token
        self._sx.resync_replay.inc()
        if _DEBUG_REPLAY:
            self._debug_verify_replay(nodes, pending, services,
                                      get_existing)
        self._maybe_checkpoint(token)
        return snap, ""

    def _debug_verify_replay(self, nodes, pending, services,
                             get_existing) -> None:
        """KTPU_DEBUG bit-identity gate: the from-scratch diff-walk over
        the authoritative pod list must be a NO-OP on a correctly
        replayed state — same planes, same vocab order, same registry —
        so the resident fingerprint must not move across it."""
        before = self._encoder.resident_fingerprint()
        self._encoder.encode(nodes, get_existing(), pending, services)
        after = self._encoder.resident_fingerprint()
        assert before == after, (
            "kube-slipstream: journal replay diverged from the "
            "authoritative re-encode")

    # -- kube-slipstream prewarm (solver/prewarm.py) ------------------------
    def _prewarm_compile(self, target: dict) -> None:
        """Prewarm-thread compile of one shape-bucket target: pad the
        latest live exemplar wave to the target and run it through the
        exact dispatch live waves use (warm_compile). Elementwise max
        against the exemplar's own dims keeps this pad-only when the
        live shape grew between queue and compile."""
        from kubernetes_tpu.solver.service import _dims_of, _pad_inputs
        snap = self._prewarm_snap
        if snap is None:
            raise RuntimeError("no exemplar wave to pad from")
        host = snapshot_to_host_inputs(snap)
        dims = _dims_of(host)
        t = {k: max(int(v), dims.get(k, 0)) for k, v in target.items()}
        for k, v in dims.items():
            t.setdefault(k, v)
        t["N1"] = t["N"] + 1
        if t["G"] > GROUP_FLOOR:
            # past its floor the group axis is no axis of its own: it
            # follows the pod bucket (IncrementalEncoder._group_bucket)
            t["G"] = max(GROUP_FLOOR, min(t["P"], self._encoder.group_cap()))
        warm_compile(_pad_inputs(host, t), snap.policy, snap.has_gangs,
                     peer_bound_of(host), mesh=self._mesh)

    def _prewarm_boot(self) -> None:
        """--prewarm boot mode: wait for the node store to fill, build a
        synthetic exemplar wave over the live cluster shape, and compile
        the pod-axis bucket ladder up to the wave size before load
        arrives (the harness gates its load window on the
        compile_prewarm_ready gauge this arms)."""
        from kubernetes_tpu.solver.prewarm import pow2_ladder
        from kubernetes_tpu.solver.service import _dims_of
        deadline = time.monotonic() + 600.0
        nodes: list = []
        while not self._stop.is_set() and time.monotonic() < deadline:
            try:
                nodes = self.config.minion_lister.list().items
            except Exception:
                nodes = []
            if nodes:
                break
            time.sleep(0.5)
        if not nodes:
            self._prewarm.boot_set([])  # nothing to imply a shape from
            return
        try:
            services = self.factory.service_store.list()
        except Exception:
            services = []
        try:
            existing = self.config.modeler.list()
        except Exception:
            existing = []
        floor = min(64, self.wave_size)
        pending = [api.Pod(metadata=api.ObjectMeta(
            name=f"prewarm-{i}", namespace="default"))
            for i in range(floor)]
        try:
            snap = encode_snapshot(nodes, existing, pending, services,
                                   policy=self.batch_policy)
            host = snapshot_to_host_inputs(snap)
        except Exception:
            _log.exception("prewarm boot: exemplar encode failed")
            self._prewarm.boot_set([])
            return
        if self._prewarm_snap is None:
            self._prewarm_snap = snap
        dims = _dims_of(host)
        targets = []
        for p in pow2_ladder(self.wave_size, floor=floor):
            t = dict(dims)
            t["P"] = p
            targets.append(t)
        self._prewarm.boot_set(targets)

    def _gate_gang_quorum(self, pods: List[api.Pod],
                          get_existing=()
                          ) -> tuple[List[api.Pod], List[api.Pod]]:
        """Split the wave into (schedulable, quorum-failed): a gang whose
        membership is below its declared min-members fails its present
        members up front (requeue + backoff) — the batch analog of a Permit
        plugin denying until quorum arrives — instead of solving a partial
        group as if it were whole.

        Quorum is aggregated per group (max of the members' declarations,
        so one unannotated member can't sneak a partial group past the
        gate) and counts already-placed members of the group from the
        cluster alongside the wave's: a straggler whose siblings bound in
        an earlier wave (or whose own bind lost a CAS race and was
        requeued) schedules once the group total reaches quorum, instead
        of starving forever on its own wave count."""
        present: dict = {}
        quorum: dict = {}
        for p in pods:
            k = gang.gang_key(p)
            if k is not None:
                present[k] = present.get(k, 0) + 1
                quorum[k] = max(quorum.get(k, 0), gang.gang_min_members(p))
        if not present or not any(quorum.values()):
            return list(pods), []  # gang-free wave: skip the O(cluster) scan
        existing = get_existing() if callable(get_existing) else get_existing
        for p in existing:
            k = gang.gang_key(p)
            if k in present and (p.status.host or p.spec.host):
                present[k] += 1
        ok: List[api.Pod] = []
        starved: List[api.Pod] = []
        for p in pods:
            k = gang.gang_key(p)
            if k is not None and present[k] < quorum[k]:
                starved.append(p)
            else:
                ok.append(p)
        return ok, starved

    # -- commit -------------------------------------------------------------
    def _split_decisions(self, pending, decisions):
        """(pod, host, victims) triples for placed pods (victims is None
        for normal placements); unschedulable pods are evented + handed to
        the error handler (backoff + requeue). ``decisions`` is a
        _WaveDecisions, or a bare host-name list from a custom solve_fn
        (which never preempts).

        kube-explain: when the wave carries its solved snapshot and some
        pod is unschedulable, the diagnosis layer (rate-limited, loop
        thread only — models/explain.Explainer) renders the k8s-idiom
        per-filter breakdown into the FailedScheduling event, replacing
        the empty-map FitError line. A declined diagnosis keeps the
        legacy message; the error handed to the requeue path is
        unchanged either way."""
        c = self.config
        if isinstance(decisions, _WaveDecisions):
            hosts, victims = decisions.hosts, decisions.victims
        else:
            hosts, victims = decisions, [None] * len(decisions)
        diag_msgs = {}
        n_unsched = sum(1 for h in hosts if h is None)
        if isinstance(decisions, _WaveDecisions) \
                and decisions.snap is not None and n_unsched:
            try:
                diag_msgs = self._explainer.diagnose_wave(
                    decisions.snap, decisions.chosen, decisions.scores,
                    n_unsched=n_unsched)
            except Exception:
                _log.exception("kube-explain diagnosis failed; falling "
                               "back to the generic FailedScheduling "
                               "message")
        placed = []
        for row, (pod, host, vict) in enumerate(zip(pending, hosts,
                                                    victims)):
            if host is None:
                err = FitError(pod, {})
                msg = diag_msgs.get(row)
                if msg is not None:
                    self._record(pod, "FailedScheduling", "%s", msg)
                else:
                    self._record(pod, "FailedScheduling",
                                 "Error scheduling: %s", err)
                c.error(pod, err)
            else:
                placed.append((pod, host, vict))
        return placed

    def _commit_wave(self, placed, tctx=None,
                     preempt_t0: Optional[float] = None):
        """Bind the wave's placements, event every outcome, assume the
        winners. Returns (outcomes, bound): outcomes[i] is None on
        success, else the bind error (aligned with ``placed``).

        kube-preempt: a placed triple carrying victims commits as an
        atomic evict+bind item (Binding.victims) — the server deletes
        every victim AND binds the pod in one transaction, or fails the
        item 409; the victims' DELETE watch events then drive kubelet
        teardown and the encoder's resident-plane removal exactly like
        any other delete."""
        with tracing.phase("wave.commit", _wave_metrics().commit,
                           parent=tctx, pods=len(placed)):
            return self._commit_wave_inner(placed, preempt_t0)

    def _commit_wave_inner(self, placed,
                           preempt_t0: Optional[float] = None):
        c = self.config
        part = _wave_metrics().part

        def mk_binding(pod, host, victims) -> api.Binding:
            refs = [api.ObjectReference(kind="Pod", namespace=v.namespace,
                                        name=v.name, uid=v.uid)
                    for v in victims] if victims else []
            return api.Binding(
                metadata=api.ObjectMeta(name=pod.metadata.name,
                                        namespace=pod.metadata.namespace),
                pod_name=pod.metadata.name, host=host, victims=refs)

        # one transactional store pass per namespace for the wave's
        # bindings (SURVEY §7 hard part (e)); the batch endpoint scopes to
        # the request namespace (authz/admission ran against it), so a
        # multi-namespace wave groups first. Per-pod CAS semantics are
        # preserved — a lost race invalidates only that pod, which requeues
        bind_many = getattr(c.binder, "bind_many", None)
        outcomes: List[Optional[Exception]] = [None] * len(placed)
        lists: list = []
        if bind_many is not None:
            with tracing.phase("wave.commit.build", part, "commit.build"):
                by_ns: dict = {}
                for idx, (pod, host, vict) in enumerate(placed):
                    by_ns.setdefault(pod.metadata.namespace, []).append(idx)
                lists = [(ns, idxs, api.BindingList(items=[
                    mk_binding(*placed[i]) for i in idxs]))
                    for ns, idxs in by_ns.items()]
        else:  # custom binder without the batch seam: reference behavior
            _wave_metrics().bind_fallback.inc()
            if not getattr(self, "_warned_bind_fallback", False):
                self._warned_bind_fallback = True
                _log.warning(
                    "binder %s has no bind_many: committing waves one "
                    "bind round-trip per pod (scheduler_bind_fallback_"
                    "total counts affected waves)",
                    type(c.binder).__name__)
        # the bind call(s) as the scheduler waits for them: the server's
        # own apiserver_batch_bind_seconds less this is HTTP and waiting
        with tracing.phase("wave.commit.bind", part, "commit.bind"):
            for ns, idxs, blist in lists:
                try:
                    results = bind_many(ns, blist)
                    for i, r in zip(idxs, results.items):
                        if r.error:
                            err = RuntimeError(r.error)
                            err.code = r.code  # CAS-vs-other classification
                            outcomes[i] = err
                        else:
                            outcomes[i] = None
                except Exception as e:
                    for i in idxs:
                        outcomes[i] = e
            if bind_many is None:
                for idx, (pod, host, vict) in enumerate(placed):
                    try:
                        c.binder.bind(mk_binding(pod, host, vict))
                    except Exception as e:
                        outcomes[idx] = e

        with tracing.phase("wave.commit.assume", part, "commit.assume"):
            # value copy before mutating (the popped pod may be shared);
            # deep_clone, not copy.deepcopy — at churn rates the stdlib
            # deepcopy was the scheduler's single largest CPU sink
            assumed = []
            for pod, host, _vict in placed:
                cl = deep_clone(pod)
                cl.spec.host = host
                cl.status.host = host
                assumed.append(cl)

            # preemption outcome accounting (scheduler_preemption_* family)
            pmx = None
            now_p = time.perf_counter()
            for (pod, host, vict), err in zip(placed, outcomes):
                if not vict:
                    continue
                if pmx is None:
                    pmx = metrics.preemption_metrics()
                if err is None:
                    pmx.attempts.inc()
                    pmx.victims.inc(by=len(vict))
                    p_prio = api.pod_priority(pod)
                    bad = sum(1 for v in vict if v.priority >= p_prio)
                    if bad:
                        pmx.higher_evictions.inc(by=bad)
                    if preempt_t0 is not None:
                        pmx.bind_seconds.observe(max(0.0, now_p - preempt_t0))
                elif getattr(err, "code", None) == 409:
                    # only true CAS losses count as conflicts; other failure
                    # classes (transport faults, 4xx validation) stay visible
                    # as requeues instead of masquerading as benign CAS churn
                    pmx.conflicts.inc()

            bound = 0
            now_m = time.monotonic()
            now_w = time.time()
            for (pod, host, _vict), cl, err in zip(placed, assumed, outcomes):
                if err is not None:
                    # lost a CAS race: requeue; next wave sees fresh state
                    self._record(pod, "FailedScheduling",
                                 "Binding rejected: %s", err)
                    c.error(pod, err)
                    continue
                self._record(pod, "Scheduled",
                             "Successfully assigned %s to %s",
                             pod.metadata.name, host)
                c.modeler.assume_pod(cl)
                bound += 1
                # pod-lifecycle latency: create -> bind committed (the
                # creationTimestamp is second-granular — fine at contract
                # load, where e2e is dominated by wave queueing), and arm
                # the bind -> watch-observe leg for the reflector hook
                ct = pod.metadata.creation_timestamp
                if ct is not None:
                    ts = ct.timestamp() if ct.tzinfo is not None else \
                        ct.replace(tzinfo=timezone.utc).timestamp()
                    self._pod_lat.e2e.observe(max(0.0, now_w - ts))
                with self._bind_t_lock:
                    obs = self._obs_t.pop(pod.metadata.uid, None)
                    if obs is None:
                        self._bind_t[pod.metadata.uid] = now_m
                        while len(self._bind_t) > self._BIND_T_MAX:
                            self._bind_t.popitem(last=False)
                if obs is not None:
                    # the watch delivery beat this arming loop (the bind was
                    # already committed server-side): the fan-out leg was
                    # effectively instantaneous relative to the commit
                    self._pod_lat.watch_observe.observe(max(0.0, obs - now_m))
        return outcomes, bound

    def schedule_wave(self, timeout: Optional[float] = None) -> int:
        """Drain, solve, commit — one wave. Returns the number of pods
        bound."""
        c = self.config
        pods = self._drain_wave(timeout)
        # one trace per wave: a bare root context (no span of its own) the
        # stage spans attach to; _drain_wave opened it for its own two
        # spans. Empty idle ticks are not waves and must not churn the
        # ring.
        tctx = self._wave_ctx(pods)
        with tracing.phase("wave.prepare", _wave_metrics().part, "prepare",
                           parent=tctx):
            prep = self._prepare_wave(pods)
        if prep is None:
            return 0
        pending, nodes, services, get_existing = prep
        try:
            if self._using_default_solve:
                # the default solve resolves `existing` lazily (delta path)
                decisions = self._default_solve(nodes, get_existing,
                                                pending, services,
                                                tctx=tctx)
            else:
                decisions = self.solve_fn(nodes, get_existing(), pending,
                                          services)
        except Exception as e:
            # a failed solve must not drop the drained wave: hand every pod
            # to the error handler for backoff+requeue, like the serial
            # driver does per pod (scheduler.go:96-101)
            for pod in pending:
                self._record(pod, "FailedScheduling",
                             "Error scheduling wave: %s", e)
                c.error(pod, e)
            return 0

        placed = self._split_decisions(pending, decisions)
        if not placed:
            return 0
        _, bound = self._commit_wave(
            placed, tctx=tctx,
            preempt_t0=decisions.t0
            if isinstance(decisions, _WaveDecisions) else None)
        return bound

    # -- loop ---------------------------------------------------------------
    def run(self) -> "BatchScheduler":
        if self._prewarm is not None:
            self._prewarm.start()
            if getattr(self.config, "prewarm", False):
                threading.Thread(target=self._prewarm_boot, daemon=True,
                                 name="tpu-batch-prewarm-boot").start()
        elif getattr(self.config, "prewarm", False):
            # remote-solver topology: the daemon compiles (and prewarms)
            # the solve programs; this worker has nothing local to warm,
            # so it reports prewarm-ready immediately for the harness's
            # readiness sweep
            self._sx.prewarm_ready.set(1)
        t = threading.Thread(target=self._loop, daemon=True,
                             name="tpu-batch-scheduler")
        t.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._prewarm is not None:
            self._prewarm.stop()

    def _loop(self) -> None:
        # per-pod and per-wave failures are evented + requeued inside
        # schedule_wave; an exception escaping to here is an infrastructure
        # fault that must not spin silently
        errs = metrics.default_registry().counter(
            "scheduler_wave_loop_errors_total",
            "exceptions escaping the tpu-batch wave loop")
        tracing.role("wave_loop")
        try:
            while not self._stop.is_set():
                try:
                    self.schedule_wave(timeout=0.2)
                except TimeoutError:
                    continue
                except Exception:
                    errs.inc()
                    _log.exception("wave loop error (backing off 10ms)")
                    time.sleep(0.01)
        finally:
            tracing.role_end()

    def _record(self, pod, reason, fmt, *args):
        if self.config.recorder is not None:
            self.config.recorder.eventf(pod, reason, fmt, *args)
