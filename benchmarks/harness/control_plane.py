"""The deployment under test, brought up in the process that holds the chip:
Master + HTTP APIServer on a loopback port + ConfigFactory reflectors +
BatchScheduler with its defaults, zero kubelets — what
``cmd/standalone.py --algorithm tpu-batch`` builds and ``chip_smoke.py``'s
served phase proved on the chip. ``feed``, ``wait_for``,
``CountingRecorder``, ``CompileLog``, ``program_counts`` and
``check_final_list`` are copies of chip_smoke.py's helpers: later PRs may
change chip_smoke.py, they may not change the yardstick.
"""

from __future__ import annotations

import contextlib
import gc
import random
import threading
import time

from benchmarks.harness import deployment as dep

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
HOST_SPAN = "bench/"     # prefix of the host spans the harness writes
# the wave loop's phases, each one call of the scheduler's own loop thread
WAVE_PHASES = {"_drain_wave": "drain", "_prepare_wave": "prepare",
               "_encode_wave": "encode", "_solve_snap": "solve",
               "_commit_wave": "commit"}


class BenchFailure(AssertionError):
    """The harness found something wrong; the run prints no result."""


def check(cond, msg: str) -> None:
    if not cond:
        raise BenchFailure(msg)


class CompileLog:
    """Every XLA backend compile of this process by jitted-function name,
    and the persistent cache's hits and misses, as jax.monitoring reports
    them. On a cache hit the 'compile' is the retrieval, near zero."""

    def __init__(self):
        import jax.monitoring as monitoring
        self._lock = threading.Lock()
        self.compiles: list = []     # (fun_name, seconds)
        self.hits = 0
        self.misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, seconds, **kw):
        if event == BACKEND_COMPILE:
            with self._lock:
                self.compiles.append((str(kw.get("fun_name", "?")),
                                      float(seconds)))

    def _on_event(self, event, **kw):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

    def mark(self) -> tuple:
        with self._lock:
            return len(self.compiles), self.hits, self.misses

    def since(self, mark: tuple) -> dict:
        with self._lock:
            new = self.compiles[mark[0]:]
            hits, misses = self.hits - mark[1], self.misses - mark[2]
        by_name: dict = {}
        for name, seconds in new:
            by_name[name] = by_name.get(name, 0.0) + seconds
        return {"xla_compiles": len(new),
                "compile_s": sum(by_name.values()),
                "by_name": {n: round(s, 3) for n, s in sorted(by_name.items())},
                "cache_hits": hits, "cache_misses": misses}


class GcLog:
    """The interpreter's full collections (generation 2) of this process:
    when each began and how long it held every thread. Since PR 28 the
    program freezes the survivors of every full collection
    (``util/gcpolicy.py``), so these walk what was made since the last one;
    the program's own series count every generation, this hook the full
    collections alone, on the harness's clock."""

    def __init__(self, annotation=None):
        self.pauses: list = []       # (began, seconds)
        self._began = None
        self._annotation = annotation   # traced runs: a host span each
        self._span = None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            self._began = time.monotonic()
            if self._annotation is not None:
                self._span = self._annotation(HOST_SPAN + "gc")
                self._span.__enter__()
        elif self._began is not None:
            self.pauses.append((self._began, time.monotonic() - self._began))
            self._began = None
            if self._span is not None:
                self._span.__exit__(None, None, None)
                self._span = None

    def close(self) -> None:
        gc.callbacks.remove(self._on_gc)

    def between(self, t0: float, t1: float) -> list:
        return [s for began, s in self.pauses if t0 <= began <= t1]


class CountingRecorder:
    """The scheduler's event recorder, counted by reason on the way."""

    def __init__(self, inner):
        self.inner = inner
        self.by_reason: dict = {}
        self.first_failure = ""
        self._lock = threading.Lock()

    def eventf(self, obj, reason, fmt, *args):
        with self._lock:
            self.by_reason[reason] = self.by_reason.get(reason, 0) + 1
            if reason != "Scheduled" and not self.first_failure:
                self.first_failure = fmt % args if args else fmt
        return self.inner.eventf(obj, reason, fmt, *args)


def program_counts() -> dict:
    from kubernetes_tpu.models.batch_solver import wave_programs
    return {f"{prog}@{plat}": int(n)
            for (prog, plat), n in wave_programs().by_label().items()}


def feed(base_url: str, create, items: list, feeders: int) -> None:
    """POST every item over HTTP from ``feeders`` threads, each with its
    own client; the first error ends the run."""
    from kubernetes_tpu.client.client import Client
    from kubernetes_tpu.client.http import HTTPTransport

    errors: list = []

    def run(part):
        client = Client(HTTPTransport(base_url))
        try:
            for obj in part:
                create(client, obj)
        except Exception as e:  # noqa: BLE001 — re-raised below
            errors.append(e)

    threads = [threading.Thread(target=run, args=(items[f::feeders],),
                                name=f"bench-nodes-{f}")
               for f in range(feeders)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]


def wait_for(predicate, timeout_s: float, what: str) -> None:
    deadline = time.monotonic() + timeout_s
    while not predicate():
        check(time.monotonic() < deadline,
              f"timed out after {timeout_s:.0f}s waiting for {what}")
        time.sleep(0.02)


def make_nodes(config: dict, seed: int) -> list:
    """The configuration's nodes, each of its own template, registered in an
    order drawn from the seed (the scheduler's node-list order is by name,
    so the order of arrival must not matter)."""
    from kubernetes_tpu.api import types as api
    from kubernetes_tpu.api.quantity import Quantity

    caps = {t["name"]: {k: Quantity(str(v))
                        for k, v in t["capacity"].items()}
            for t in dep.node_templates(config)}
    nodes = [api.Node(
        metadata=api.ObjectMeta(name=name, labels=dict(t["labels"])),
        spec=api.NodeSpec(capacity=dict(caps[t["name"]])))
        for name, t in dep.nodes_of(config).items()]
    random.Random(seed).shuffle(nodes)
    return nodes


def make_services(config: dict) -> list:
    from kubernetes_tpu.api import types as api

    return [api.Service(
        metadata=api.ObjectMeta(name=s["name"], namespace=s["namespace"]),
        spec=api.ServiceSpec(port=80, selector=dict(s["selector"])))
        for s in dep.services(config)]


class ControlPlane:
    """Bring-up and tear-down. ``sched`` is the BatchScheduler the window
    drives; ``waves`` is what its solves decided, recorded from here."""

    def __init__(self, config: dict, seed: int, clog: CompileLog):
        from kubernetes_tpu.api import types as api
        from kubernetes_tpu.apiserver.http import APIServer
        from kubernetes_tpu.apiserver.master import Master, MasterConfig
        from kubernetes_tpu.client.client import Client
        from kubernetes_tpu.client.http import HTTPTransport
        from kubernetes_tpu.client.record import (AsyncEventRecorder,
                                                  EventRecorder)
        from kubernetes_tpu.scheduler.driver import ConfigFactory
        from kubernetes_tpu.scheduler.tpu_batch import BatchScheduler

        self.config = config
        self.clog = clog
        self.factory = self.sched = self.events = None
        self.waves: list = []
        self.srv = APIServer(Master(MasterConfig()), host="127.0.0.1",
                             port=0).start()
        try:
            t0 = time.perf_counter()
            feed(self.srv.base_url, lambda c, o: c.nodes().create(o),
                 make_nodes(config, seed), 4)
            self.register_nodes_s = time.perf_counter() - t0
            services = make_services(config)
            if services:
                feed(self.srv.base_url, lambda c, o: c.services(
                    o.metadata.namespace).create(o), services, 1)
            # the scheduler as cmd/scheduler builds it: its own HTTP
            # client, rate-limited async events, default wave size, linger
            client = Client(HTTPTransport(self.srv.base_url,
                                          user_agent="kube-scheduler"))
            self.events = AsyncEventRecorder(
                EventRecorder(client, api.EventSource(
                    component=api.DefaultSchedulerName)),
                qps=50.0, burst=100)
            self.recorder = CountingRecorder(self.events)
            self.factory = ConfigFactory(client)
            self.sched = BatchScheduler(
                self.factory.create(recorder=self.recorder), self.factory,
                client)
            self._record_waves()
            self._gate = threading.Event()
            self._gate.set()
            self._install_gate()
            wait_for(lambda: len(self.factory.node_store.list())
                     == int(config["nodes"])
                     and len(self.factory.service_store.list())
                     == len(services), 120.0,
                     "the scheduler's node and service watches to hold "
                     "every node and service")
            self.sched.run()
        except BaseException:
            self.stop()
            raise

    def _record_waves(self) -> None:
        """What each timed wave decided, taken where it is produced: the
        instance's ``_default_solve`` (encode, then ``_solve_snap``) is
        wrapped; pending pods in wave order, hosts and scores."""
        inner = self.sched._default_solve
        waves = self.waves

        def recording(nodes, existing, pending, services, tctx=None):
            decisions = inner(nodes, existing, pending, services, tctx=tctx)
            waves.append({
                "t": time.monotonic(),
                "pods": [p.metadata.name for p in pending],
                "hosts": list(decisions.hosts),
                "scores": [int(s) for s in
                           decisions.scores[:len(pending)]],
                "dims": _wave_dims(decisions.snap, len(pending),
                                   len(nodes))})
            return decisions

        self.sched._default_solve = recording

    def annotate_phases(self, annotation) -> None:
        """Traced runs only: each phase of the wave loop as a host span in
        the profiler's own trace, written from here round the instance's
        methods (the program writes no annotation yet), so that the
        device's idle gaps can be given to what the host was doing."""
        for attr, phase in WAVE_PHASES.items():
            def spanned(*a, _inner=getattr(self.sched, attr),
                        _name=HOST_SPAN + phase, **kw):
                with annotation(_name):
                    return _inner(*a, **kw)
            setattr(self.sched, attr, spanned)

    def _install_gate(self) -> None:
        """A gate before the wave loop's ``next_pod``: open, it passes every
        call through; shut (set-up only), the loop waits."""
        config, inner, gate = self.sched.config, \
            self.sched.config.next_pod, self._gate

        def gated(timeout=None):
            if not gate.wait(timeout):
                raise TimeoutError("the warm-up gate is shut")
            return inner(timeout)

        config.next_pod = gated

    @contextlib.contextmanager
    def gate_shut(self):
        """Set-up only: a warm-up round is created behind the shut gate and
        reaches the wave loop whole, as one wave."""
        self._gate.clear()
        # the loop may have passed the open gate and be inside its 0.2 s
        # pop on the empty queue: let that one run out
        time.sleep(0.25)
        try:
            yield
        finally:
            self._gate.set()

    def queued(self) -> int:
        return len(self.factory.pod_queue.list())

    def prewarm_idle(self) -> bool:
        pre = self.sched._prewarm
        return pre is None or pre.pending() == 0

    def metrics_text(self) -> str:
        """Every series of the process, as the /metrics endpoint renders
        them: the default registry and the apiserver's own."""
        from kubernetes_tpu.util import metrics
        return (metrics.default_registry().render_text()
                + self.srv.metrics_registry.render_text())

    def final_lists(self):
        from kubernetes_tpu.api import types as api
        from kubernetes_tpu.client.client import Client
        from kubernetes_tpu.client.http import HTTPTransport
        user = Client(HTTPTransport(self.srv.base_url))
        return (user.pods(api.NamespaceAll).list().items,
                user.nodes().list().items)

    def stop(self) -> None:
        if self.sched is not None:
            self.sched.stop()
            for t in threading.enumerate():
                if t.name == "sched-prewarm-compile":
                    t.join(timeout=300.0)
        if self.factory is not None:
            self.factory.stop()
        if self.events is not None:
            self.events.stop()
        self.srv.stop()


def _wave_dims(snap, n_pending: int, n_nodes: int) -> dict:
    """The wave's sizes for the roofline: true and padded pod and node
    counts, and the resource dimensions."""
    dims = {"pods": n_pending, "nodes": n_nodes}
    req = getattr(snap, "req", None)
    if req is not None and hasattr(req, "shape"):
        dims["P"], dims["R"] = int(req.shape[0]), int(req.shape[1])
    cap = getattr(snap, "cap", None)
    if cap is not None and hasattr(cap, "shape"):
        dims["N"] = int(cap.shape[0])
    return dims


def check_final_list(pods, nodes) -> dict:
    """The served result from one LIST, with no solver code: which pod sits
    on which node, nodes over a capacity they state (every resource of it),
    host ports taken twice."""
    def amount(resource, quantity):
        return quantity.milli_value() if resource == "cpu" \
            else quantity.int_value()

    cap = {n.metadata.name: {r: amount(r, q)
                             for r, q in n.spec.capacity.items()}
           for n in nodes}
    where: dict = {}
    used: dict = {}
    ports: dict = {}
    port_clashes = unknown_nodes = twice = 0
    for p in pods:
        name, host = p.metadata.name, p.spec.host
        if name in where:
            twice += 1
        where[name] = host or None
        if not host:
            continue
        if host != p.status.host or host not in cap:
            unknown_nodes += 1
            continue
        sums = used.setdefault(host, {})
        for c in p.spec.containers:
            for r, q in c.resources.limits.items():
                sums[r] = sums.get(r, 0) + amount(r, q)
            for port in c.ports:
                if port.host_port:
                    taken = ports.setdefault(host, set())
                    port_clashes += port.host_port in taken
                    taken.add(port.host_port)
    over = sum(1 for h, sums in used.items()
               if any(sums.get(r, 0) > c for r, c in cap[h].items()))
    return {"where": where, "nodes_over_capacity": over,
            "host_port_clashes": port_clashes,
            "bound_to_unknown_node": unknown_nodes, "listed_twice": twice,
            "nodes_used": len(used),
            "max_cpu_share": max((sums.get("cpu", 0) / cap[h]["cpu"]
                                  for h, sums in used.items()
                                  if cap[h].get("cpu")), default=0.0)}
