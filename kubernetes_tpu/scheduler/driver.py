"""The scheduler driver + factory.

Rebuild of ``plugin/pkg/scheduler/`` — the harness around the pure algorithm:

- ``Scheduler.schedule_one`` (scheduler.go:90-119): blocking FIFO pop ->
  Algorithm.schedule -> POST binding -> Modeler.assume_pod, with events on
  every outcome.
- ``SimpleModeler`` (modeler.go:56-155): the optimistic "assumed pods" cache
  bridging bind -> watch-confirmation latency.
- ``PodBackoff`` (factory.go:245-369): per-pod exponential backoff 1s -> 60s
  with gc; the default error handler re-fetches and re-queues.
- ``ConfigFactory`` (factory.go:40-172): wires reflectors (unassigned pods ->
  FIFO via field selector spec.host=; assigned pods -> store; nodes -> store
  through the Schedulable/Ready filter of factory.go:203-238, where the
  reference polled every 10 s; services -> store).

The ``algorithm`` seam accepts anything with ``schedule(pod, minion_lister)``
— the serial GenericScheduler or the TPU-backed batch adapter — so both sit
behind identical plumbing.
"""

from __future__ import annotations

import logging
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional

from kubernetes_tpu.api import errors
from kubernetes_tpu.api import labels as labels_pkg
from kubernetes_tpu.api import types as api
from kubernetes_tpu.client.cache import (
    FIFO,
    Reflector,
    Store,
    StorePodLister,
    StoreServiceLister,
    meta_namespace_key_func,
)
from kubernetes_tpu.client.record import EventRecorder
from kubernetes_tpu.runtime.clone import deep_clone
from kubernetes_tpu.scheduler import plugins as schedplugins
from kubernetes_tpu.scheduler.generic import GenericScheduler
from kubernetes_tpu.util import metrics

_log = logging.getLogger("kubernetes_tpu.scheduler")

__all__ = ["Scheduler", "SchedulerConfig", "SimpleModeler", "PodBackoff",
           "ConfigFactory", "filter_schedulable_nodes", "node_is_schedulable"]


class SimpleModeler:
    """ref: modeler.go:56-155."""

    def __init__(self, queued_pods: FIFO, scheduled_pods: Store):
        self.queued = queued_pods
        self.scheduled = scheduled_pods
        self.assumed = Store()

    def assume_pod(self, pod: api.Pod) -> None:
        self.assumed.add(pod)

    def _prune_assumed(self) -> None:
        """Drop assumed pods once seen in the queued or scheduled stores
        (ref: modeler.go:90-139 listPods)."""
        for pod in self.assumed.list():
            key = meta_namespace_key_func(pod)
            if self.queued.get_by_key(key) is not None:
                self.assumed.delete(pod)
            elif self.scheduled.get_by_key(key) is not None:
                self.assumed.delete(pod)

    def list(self, selector: Optional[labels_pkg.Selector] = None):
        self._prune_assumed()
        scheduled = StorePodLister(self.scheduled).list(selector)
        assumed = StorePodLister(self.assumed).list(selector)
        return scheduled + assumed

    # -- O(changed) view -----------------------------------------------------
    def token(self):
        """Changelog position over both stores; pair with delta()."""
        return (self.scheduled.token(), self.assumed.token())

    def delta(self, token):
        """Events on the COMBINED (scheduled + assumed) pod set since
        ``token``: -> (upserted_pods, removed_pods, new_token), or None
        only when the log window was exceeded (resync via list()).
        kube-slipstream: a reflector relist is NOT a window break any
        more — Store.replace diffs the new list against the cache and
        appends only the real changes to the changelog, so watch 410s
        and stream resets replay through this same O(changed) path
        (scheduler/tpu_batch.py _replay_resync) instead of forcing a
        full re-encode; delta() returns None only when the gap truly
        outgrew the ring. Consumers MUST apply upserts before removes. A
        delete event is suppressed while the pod's key is live in either
        store — an assumed pod disappearing because the reflector caught
        its binding (prune) is a migration, and a delete+set pair inside
        one window is a resurrection, not a removal."""
        self._prune_assumed()
        ds = self.scheduled.delta_since(token[0])
        da = self.assumed.delta_since(token[1])
        if ds is None or da is None:
            return None
        upserted, removed = [], []
        for events in (ds[0], da[0]):
            for op, pod in events:
                if op == "set":
                    upserted.append(pod)
                else:
                    key = meta_namespace_key_func(pod)
                    live = self.scheduled.get_by_key(key) \
                        or self.assumed.get_by_key(key)
                    # suppress only when the SAME uid is still live: a
                    # delete + recreate of the name inside one window is a
                    # new pod — the old uid must still be removed or its
                    # resources leak in the encoder
                    if live is None or live.metadata.uid != pod.metadata.uid:
                        removed.append(pod)
        return upserted, removed, (ds[1], da[1])

    def pod_lister(self):
        return self


class PodBackoff:
    """ref: factory.go:245-268,320-369 — exponential 1s -> 60s + gc."""

    def __init__(self, initial: float = 1.0, max_duration: float = 60.0,
                 clock: Callable[[], float] = time.monotonic):
        self.initial = initial
        self.max_duration = max_duration
        self.clock = clock
        self._lock = threading.Lock()
        self._entries: Dict[str, list] = {}  # key -> [backoff_seconds, last_update]

    def get_backoff(self, pod_key: str) -> float:
        """Returns the duration to wait, doubling for next time."""
        with self._lock:
            entry = self._entries.setdefault(pod_key, [self.initial, self.clock()])
            duration = entry[0]
            entry[0] = min(entry[0] * 2, self.max_duration)
            entry[1] = self.clock()
            return duration

    def gc(self, max_age: float = 60.0) -> None:
        with self._lock:
            now = self.clock()
            for key in [k for k, e in self._entries.items() if now - e[1] > max_age]:
                del self._entries[key]


@dataclass
class SchedulerConfig:
    """ref: scheduler.go:55-75 Config — the full DI seam for tests."""

    modeler: SimpleModeler = None
    minion_lister: object = None
    algorithm: object = None                       # .schedule(pod, minion_lister)
    binder: object = None                          # .bind(binding)
    next_pod: Callable[[], api.Pod] = None
    error: Callable[[api.Pod, Exception], None] = None
    recorder: Optional[EventRecorder] = None
    # what the config was built from, so alternate drivers (tpu_batch) can
    # refuse configurations they cannot model instead of silently solving
    # the default-provider problem
    provider: str = schedplugins.DEFAULT_PROVIDER
    policy: Optional[schedplugins.Policy] = None
    # HOST:PORT of a shared kube-solverd daemon; empty = solve in-process.
    # Recorded here (not on the driver) so any wave-capable driver built
    # from this config inherits the cluster's solver topology.
    solver_addr: str = ""
    # What a wave does when the daemon is away (kube-scheduler
    # --solver-fallback): "inprocess" solves the wave locally (the
    # original degradation ladder — correct when no supervisor will
    # bring the daemon back, but at full shape the cold in-process
    # compile can stall the worker for minutes), "requeue" fails the
    # wave instead — every pod requeues through the error handler and
    # the next wave retries the daemon, which a kube-chaos supervisor
    # respawns within seconds (docs/design/ha.md). CAS-convergent
    # either way.
    solver_fallback: str = "inprocess"
    # Device-mesh solve for the IN-PROCESS path (kube-scheduler --mesh):
    # "auto" shards waves above parallel.mesh.DEFAULT_MESH_MIN_NODES over
    # the attached device mesh when >1 device exists, "on" requires one,
    # "off" pins single-device. A solver_addr daemon carries its own
    # --mesh flag; this one covers workers solving in-process (and the
    # RemoteSolver fallback path). Decisions are bit-identical either way
    # (parallel/mesh.py contract).
    mesh: str = "auto"
    pods_axis: int = 1
    # kube-slipstream (kube-scheduler --prewarm): compile the wave-size
    # bucket ladder implied by the live cluster at boot, off the wave
    # loop, before the harness opens its load window (scheduler/
    # tpu_batch.py _prewarm_boot; compile_prewarm_ready on /metrics).
    prewarm: bool = False


class Scheduler:
    """ref: scheduler.go:78-119."""

    def __init__(self, config: SchedulerConfig):
        self.config = config
        self._stop = threading.Event()

    def run(self) -> "Scheduler":
        t = threading.Thread(target=self._loop, daemon=True, name="scheduler")
        t.start()
        return self

    def stop(self) -> None:
        self._stop.set()

    def _loop(self) -> None:
        # per-pod failures are evented + requeued inside schedule_one
        # (c.error); anything escaping to here is an infrastructure fault
        # that must not spin silently (ref: util.HandleCrash + glog — every
        # reference loop logs its crashes, scheduler.go:90-119)
        errs = metrics.default_registry().counter(
            "scheduler_loop_errors_total",
            "exceptions escaping the serial scheduling loop")
        while not self._stop.is_set():
            try:
                self.schedule_one(timeout=0.2)
            except TimeoutError:
                continue
            except Exception:
                errs.inc()
                _log.exception("scheduler loop error (backing off 10ms)")
                time.sleep(0.01)

    def _record(self, pod, reason, fmt, *args):
        if self.config.recorder is not None:
            self.config.recorder.eventf(pod, reason, fmt, *args)

    def schedule_one(self, timeout: Optional[float] = None) -> Optional[str]:
        """ref: scheduler.go:90-119 scheduleOne."""
        c = self.config
        pod = c.next_pod() if timeout is None else c.next_pod(timeout)
        try:
            dest = c.algorithm.schedule(pod, c.minion_lister)
        except Exception as e:
            self._record(pod, "FailedScheduling", "Error scheduling: %s", e)
            c.error(pod, e)
            return None
        binding = api.Binding(
            metadata=api.ObjectMeta(name=pod.metadata.name,
                                    namespace=pod.metadata.namespace),
            pod_name=pod.metadata.name, host=dest)
        try:
            c.binder.bind(binding)
        except Exception as e:
            self._record(pod, "FailedScheduling", "Binding rejected: %s", e)
            c.error(pod, e)
            return None
        self._record(pod, "Scheduled", "Successfully assigned %s to %s",
                     pod.metadata.name, dest)
        # copy before mutating, like the reference's `assumed := *pod`
        # (scheduler.go:114-117) — the popped pod may be shared
        assumed = deep_clone(pod)
        assumed.spec.host = dest
        assumed.status.host = dest
        c.modeler.assume_pod(assumed)
        return dest


def node_is_schedulable(node: api.Node) -> bool:
    """ref: factory.go:203-238 pollMinions, for one node — its Schedulable
    condition isn't false and it is Ready (or Reachable, or carries no
    conditions at all), and it is not cordoned (``spec.unschedulable``,
    kubectl cordon). The scheduler's own Schedulable predicate and the
    dense ``node_extra_ok`` fold are the belt to this filter's suspenders:
    a cordon reaches the node store one watch hop after the apiserver
    took it, and a wave cut inside that hop must not bind onto the node."""
    if node.spec.unschedulable:
        return False
    conds = {c.type: c for c in node.status.conditions}
    sched = conds.get(api.NodeSchedulable)
    if sched is not None and sched.status != api.ConditionTrue:
        return False
    for kind in (api.NodeReady, api.NodeReachable):
        cond = conds.get(kind)
        if cond is not None:
            return cond.status == api.ConditionTrue
    return True


def filter_schedulable_nodes(nodes: api.NodeList) -> api.NodeList:
    """The nodes of a list that pass ``node_is_schedulable``."""
    return api.NodeList(
        items=[n for n in nodes.items if node_is_schedulable(n)])


class _SchedulableNodes:
    """What the node reflector writes through: ``node_is_schedulable``
    applied at ingest, so the node store only ever holds nodes a wave may
    use and nothing filters at list time. A node that stops passing (a
    cordon, a condition gone false) leaves the store with the event that
    says so; one that passes again comes back the same way.

    Counts what the node source decoded, by how it arrived:
    ``scheduler_node_source_objects_total{via="list"|"watch"}``, and the
    LISTs after the first, ``scheduler_node_source_relists_total`` (a 410,
    an ERROR event or a stream closed cold: ``Reflector``'s conditions)."""

    def __init__(self, store: Store):
        self.store = store
        reg = metrics.default_registry()
        self._objects = reg.counter(
            "scheduler_node_source_objects_total",
            "Node objects the scheduler's node source decoded, by how "
            "they arrived: in a LIST of every node, or one in a watch event",
            ("via",))
        self._relists = reg.counter(
            "scheduler_node_source_relists_total",
            "LISTs of every node the scheduler's node source made after "
            "its first (its watch expired, broke or closed cold)")
        self._listed = False

    def add(self, node: api.Node) -> None:
        self._objects.inc("watch")
        if node_is_schedulable(node):
            self.store.add(node)
        else:
            self.store.delete(node)

    update = add

    def delete(self, node: api.Node) -> None:
        self._objects.inc("watch")
        self.store.delete(node)

    def replace(self, nodes) -> None:
        self._objects.inc("list", by=len(nodes))
        if self._listed:
            self._relists.inc()
        self._listed = True
        self.store.replace([n for n in nodes if node_is_schedulable(n)])


class _StoreMinionLister:
    """The node store's nodes by name. The sorted list is kept until the
    store's token moves: a wave over an unchanged cluster gets the list
    (and so the node objects) it got last time, which is what keeps the
    encoder's ``_nodes_changed`` on its identity path."""

    def __init__(self, store: Store):
        self.store = store
        self._kept = (None, None)      # (store token, NodeList)

    def list(self) -> api.NodeList:
        # the token first: a write between the two reads leaves a list
        # newer than its token, which the next call sorts again
        token = self.store.token()
        kept_token, kept = self._kept
        if token != kept_token:
            kept = api.NodeList(items=sorted(
                self.store.list(), key=lambda n: n.metadata.name))
            self._kept = (token, kept)
        return kept


class ConfigFactory:
    """ref: factory.go:40-172 ConfigFactory/CreateFromKeys.

    Every source is a ``Reflector``: one LIST at start, then watch events.
    ``node_poll_period`` is accepted and unused: it was the period of the
    LIST of every node that the node source made before it watched
    (ROADMAP.md D11 has the caller that still passes it)."""

    def __init__(self, client, node_poll_period: float = 10.0):
        self.client = client
        # unassigned pods; how long each waited for a wave is the one
        # queueing delay on the timed path
        self.pod_queue = FIFO(wait_hist=metrics.default_registry().histogram(
            "scheduler_queue_wait_seconds",
            "Seconds a pod sat in the scheduler's FIFO: first add of its "
            "key to the pop that handed it to a wave",
            buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                     0.5, 1.0, 2.5, 5.0, 10.0)))
        self.scheduled_pods = Store()        # assigned pods
        self.node_store = Store()
        self.service_store = Store()
        self.modeler = SimpleModeler(self.pod_queue, self.scheduled_pods)
        self.backoff = PodBackoff()
        self._runners = []
        # backoff-requeue threads (error handler): tracked so stop() can
        # wake them early (they sleep on this event, not time.sleep) and
        # join them — a requeue outliving its factory would re-fetch
        # against a torn-down apiserver and stack-trace in a daemon thread
        self._stopping = threading.Event()
        self._requeue_threads: list = []
        self._requeue_lock = threading.Lock()

    def create(self, provider: str = schedplugins.DEFAULT_PROVIDER,
               policy: Optional[schedplugins.Policy] = None,
               algorithm_override=None,
               recorder: Optional[EventRecorder] = None,
               solver_addr: str = "",
               mesh: str = "auto", pods_axis: int = 1,
               solver_fallback: str = "inprocess",
               prewarm: bool = False) -> SchedulerConfig:
        """ref: factory.go:77-172 CreateFromProvider/CreateFromConfig/
        CreateFromKeys."""
        # reflector: unassigned pods -> FIFO (field selector spec.host=)
        self._runners.append(Reflector(
            self.client.pods(api.NamespaceAll).list_watch(field_selector="spec.host="),
            self.pod_queue, name="unassigned-pods").run())
        # reflector: assigned pods -> store
        self._runners.append(Reflector(
            self.client.pods(api.NamespaceAll).list_watch(field_selector="spec.host!="),
            self.scheduled_pods, name="assigned-pods").run())
        # reflector: nodes -> store through the schedulable filter (the
        # reference polled here, factory.go:139). node_store holds the
        # first LIST, if it could be made, when create() returns
        nodes = Reflector(self.client.nodes().list_watch(),
                          _SchedulableNodes(self.node_store),
                          name="nodes").run()
        self._runners.append(nodes)
        # reflector: services
        self._runners.append(Reflector(
            self.client.services(api.NamespaceAll).list_watch(),
            self.service_store, name="services").run())
        nodes.wait_listed()

        minion_lister = _StoreMinionLister(self.node_store)
        pod_lister = self.modeler.pod_lister()
        args = schedplugins.PluginFactoryArgs(
            pod_lister=pod_lister,
            service_lister=StoreServiceLister(self.service_store),
            node_lister=minion_lister,
            node_info=_NodeStoreInfo(self.node_store))

        if algorithm_override is not None:
            algorithm = algorithm_override(args)
        elif policy is not None:
            algorithm = GenericScheduler(
                schedplugins.predicates_from_policy(policy, args),
                schedplugins.priorities_from_policy(policy, args), pod_lister)
        else:
            keys = schedplugins.get_algorithm_provider(provider)
            algorithm = GenericScheduler(
                schedplugins.get_predicates(keys["predicates"], args),
                schedplugins.get_priorities(keys["priorities"], args), pod_lister)

        return SchedulerConfig(
            modeler=self.modeler,
            minion_lister=minion_lister,
            algorithm=algorithm,
            binder=_Binder(self.client),
            next_pod=self._next_pod,
            error=self._make_error_func(),
            recorder=recorder,
            provider=provider,
            policy=policy,
            solver_addr=solver_addr,
            solver_fallback=solver_fallback,
            mesh=mesh,
            pods_axis=pods_axis,
            prewarm=prewarm,
        )

    def stop(self, join: bool = False, timeout: float = 2.0) -> bool:
        """Stop every reflector. With ``join=True``, wait for their
        threads to exit so no in-flight watch delivery can land in the
        stores afterwards — the deterministic-freeze contract the
        stale-wave tests rely on. Returns False iff a join timed out
        (the freeze is then NOT guaranteed).

        Backoff-requeue threads are always woken (they wait on the stop
        event instead of sleeping) and joined, so a stopped factory never
        leaves a daemon thread behind to re-fetch from a torn-down
        apiserver."""
        self._stopping.set()
        for r in self._runners:
            r.stop()
        frozen = True
        if join:
            for r in self._runners:
                if not r.join(timeout):
                    frozen = False
        with self._requeue_lock:
            requeues = list(self._requeue_threads)
        for t in requeues:
            t.join(timeout)
            if t.is_alive() and join:
                frozen = False
        return frozen

    def _next_pod(self, timeout: Optional[float] = None) -> api.Pod:
        """ref: factory.go:164-168 — blocking FIFO pop."""
        return self.pod_queue.pop(timeout=timeout)

    def _make_error_func(self):
        """ref: factory.go makeDefaultErrorFunc — backoff, re-fetch, re-queue
        if still unscheduled."""

        def handle(pod: api.Pod, err: Exception) -> None:
            if self._stopping.is_set():
                return
            key = meta_namespace_key_func(pod)
            delay = self.backoff.get_backoff(key)

            def requeue():
                # stop() wakes this immediately — no orphaned sleeper
                if self._stopping.wait(delay):
                    return
                try:
                    fresh = self.client.pods(pod.metadata.namespace).get(pod.metadata.name)
                    if not fresh.spec.host:
                        self.pod_queue.add(fresh)
                except errors.StatusError:
                    pass  # deleted meanwhile
                except OSError:
                    pass  # apiserver unreachable (shutdown race): drop —
                    #       a live pod relists into the queue on reconnect
                self.backoff.gc()

            t = threading.Thread(target=requeue, daemon=True,
                                 name="scheduler-requeue")
            with self._requeue_lock:
                self._requeue_threads[:] = [x for x in self._requeue_threads
                                            if x.is_alive()]
                self._requeue_threads.append(t)
            t.start()

        return handle


class _Binder:
    """ref: factory.go:297-308 binder — POST /bindings."""

    def __init__(self, client):
        self.client = client

    def bind(self, binding: api.Binding) -> None:
        self.client.pods(binding.metadata.namespace).bind(binding)

    def bind_many(self, namespace: str,
                  bindings: api.BindingList) -> api.BindingResultList:
        """Commit one namespace's wave bindings in one transactional store
        pass (the batch seam the tpu-batch scheduler uses; per-pod CAS
        semantics kept)."""
        return self.client.pods(namespace).bind_many(bindings)


class _NodeStoreInfo:
    """NodeInfo over the scheduler's node store (GetNodeInfo by name)."""

    def __init__(self, store: Store):
        self.store = store

    def get_node_info(self, name: str) -> api.Node:
        node = self.store.get_by_key(name)
        if node is None:
            raise KeyError(f"unknown node {name!r}")
        return node
