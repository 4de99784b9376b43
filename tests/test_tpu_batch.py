"""BatchScheduler (tpu-batch profile) driving a live cluster on CPU."""

import time

import pytest

from kubernetes_tpu.api import types as api
from kubernetes_tpu.api.quantity import Quantity
from kubernetes_tpu.apiserver.master import Master
from kubernetes_tpu.client.client import Client, InProcessTransport
from kubernetes_tpu.models import gang as gang_mod
from kubernetes_tpu.models.oracle import solve_serial
from kubernetes_tpu.runtime.clone import deep_clone
from kubernetes_tpu.scheduler.driver import ConfigFactory, PodBackoff
from kubernetes_tpu.scheduler.tpu_batch import BatchScheduler


def mk_node(name, cpu="8", mem="16Gi"):
    return api.Node(metadata=api.ObjectMeta(name=name),
                    spec=api.NodeSpec(capacity={"cpu": Quantity(cpu),
                                                "memory": Quantity(mem)}))


def mk_pod(name, app="web"):
    return api.Pod(
        metadata=api.ObjectMeta(name=name, namespace="default",
                                labels={"app": app}),
        spec=api.PodSpec(containers=[api.Container(
            name="c", image="i",
            resources=api.ResourceRequirements(limits={
                "cpu": Quantity("500m"), "memory": Quantity("512Mi")}))]))


def _wait(pred, timeout=15.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.05)
    return False


def test_batch_scheduler_schedules_and_spreads():
    m = Master()
    client = Client(InProcessTransport(m))
    for i in range(4):
        client.nodes().create(mk_node(f"n{i}"))
    client.services().create(api.Service(
        metadata=api.ObjectMeta(name="web", namespace="default"),
        spec=api.ServiceSpec(port=80, selector={"app": "web"})))
    factory = ConfigFactory(client)
    config = factory.create()
    sched = BatchScheduler(config, factory, client, wave_size=64,
                           wave_linger_s=0.1).run()
    try:
        time.sleep(0.3)  # let reflectors sync
        for i in range(12):
            client.pods().create(mk_pod(f"w{i}"))
        assert _wait(lambda: all(p.spec.host for p in client.pods().list().items))
        placement = {}
        for p in client.pods().list().items:
            placement[p.spec.host] = placement.get(p.spec.host, 0) + 1
        # 12 service pods over 4 nodes: perfect spread
        assert sorted(placement.values()) == [3, 3, 3, 3], placement
    finally:
        sched.stop()
        factory.stop()


def test_batch_scheduler_requeues_unschedulable():
    m = Master()
    client = Client(InProcessTransport(m))
    client.nodes().create(mk_node("tiny", cpu="1", mem="1Gi"))
    factory = ConfigFactory(client)
    factory.backoff = PodBackoff(initial=0.05, max_duration=0.2)
    config = factory.create()
    sched = BatchScheduler(config, factory, client, wave_size=8,
                           wave_linger_s=0.05).run()
    try:
        big = mk_pod("big")
        big.spec.containers[0].resources.limits["cpu"] = Quantity("4")
        client.pods().create(big)
        time.sleep(0.4)
        assert client.pods().get("big").spec.host == ""
        client.nodes().create(mk_node("huge", cpu="32", mem="64Gi"))
        assert _wait(lambda: client.pods().get("big").spec.host == "huge")
    finally:
        sched.stop()
        factory.stop()


def test_batch_scheduler_many_service_groups():
    """A wave spanning hundreds of service groups must schedule to
    completion — the encoder pads the group axis instead of refusing
    (round-1 weakness: >64 groups raised and the whole wave requeued
    forever)."""
    n_services = 200
    m = Master()
    client = Client(InProcessTransport(m))
    for i in range(8):
        client.nodes().create(mk_node(f"n{i}", cpu="64", mem="128Gi"))
    for s in range(n_services):
        client.services().create(api.Service(
            metadata=api.ObjectMeta(name=f"svc-{s:03d}", namespace="default"),
            spec=api.ServiceSpec(port=80, selector={"app": f"app-{s:03d}"})))
    factory = ConfigFactory(client)
    config = factory.create()
    sched = BatchScheduler(config, factory, client, wave_size=256,
                           wave_linger_s=0.2).run()
    try:
        time.sleep(0.3)  # let reflectors sync
        for s in range(n_services):
            client.pods().create(mk_pod(f"p{s:03d}", app=f"app-{s:03d}"))
        assert _wait(lambda: all(p.spec.host
                                 for p in client.pods().list().items),
                     timeout=30.0), "wave with 200 service groups stalled"
    finally:
        sched.stop()
        factory.stop()


def test_encode_many_groups_matches_serial():
    """Encoder-level: 150 groups in one wave, decisions bit-identical."""
    import numpy as np

    from kubernetes_tpu.models.batch_solver import (
        decisions_to_names, snapshot_to_inputs, solve_jit)
    from kubernetes_tpu.models.oracle import solve_serial
    from kubernetes_tpu.models.snapshot import encode_snapshot

    nodes = [mk_node(f"n{i}", cpu="64", mem="128Gi") for i in range(10)]
    services = [api.Service(
        metadata=api.ObjectMeta(name=f"s{k}", namespace="default"),
        spec=api.ServiceSpec(port=80, selector={"app": f"a{k}"}))
        for k in range(150)]
    pending = [mk_pod(f"p{k}", app=f"a{k}") for k in range(150)]
    snap = encode_snapshot(nodes, [], pending, services)
    assert snap.group_counts.shape[0] >= 150  # padded pow2 bucket
    chosen, _ = solve_jit(snapshot_to_inputs(snap))
    batch = decisions_to_names(snap, np.asarray(chosen))
    assert batch == solve_serial(nodes, [], pending, services)


# -- the loop under injected faults, against the serial oracle ---------------
#
# Every wave the loop solved is recorded where it is produced (the
# instance's _default_solve: the nodes and the ordered pending pods it was
# given, the hosts it decided) and replayed through models/oracle.py from
# the empty cluster: the oracle sees, as existing pods, exactly the binds
# that had succeeded before that wave (and whatever a fault put into the
# store). The loop's committed decisions must equal the oracle's wave by
# wave, whatever the waves turned out to hold.

N_FAULT_NODES = 12


def mk_fault_pod(i, prefix="p"):
    return api.Pod(
        metadata=api.ObjectMeta(name=f"{prefix}{i:05d}", namespace="default",
                                uid=f"uid-{prefix}{i:05d}"),
        spec=api.PodSpec(containers=[api.Container(
            name="c", image="img",
            resources=api.ResourceRequirements(limits={
                "cpu": Quantity(f"{100 + (i % 8) * 100}m"),
                "memory": Quantity(f"{128 + (i % 4) * 64}Mi")}))]))


def mk_gang_pods():
    pods = []
    for g in range(24):
        for member in range(4):
            p = mk_fault_pod(g * 4 + member, prefix="g")
            p.metadata.annotations = {
                gang_mod.GANG_NAME_ANNOTATION: f"group-{g:03d}",
                gang_mod.GANG_MIN_MEMBERS_ANNOTATION: "4"}
            pods.append(p)
    return pods


class _FailOnceBinder:
    """Deterministic CAS-loss injection: the named pod's first bind is
    rejected (as if another scheduler won the race); every other bind
    passes through. Exposes only .bind, so the loop commits pod by pod."""

    def __init__(self, inner, fail_name):
        self._inner = inner
        self._fail_name = fail_name
        self.failed = 0

    def bind(self, binding):
        if binding.pod_name == self._fail_name and self.failed == 0:
            self.failed += 1
            raise RuntimeError("injected CAS conflict: binding rejected")
        return self._inner.bind(binding)


class _InjectingSolver:
    """Deterministic store delta during a solve: the FIRST wave's solve
    lands a foreign assigned pod (another scheduler's bind, as the
    reflector would deliver it) in the modeler's scheduled store before
    returning. Wave 1 was encoded before it; wave 2 must account for it."""

    def __init__(self, factory):
        self._factory = factory
        self.foreign = None

    def solve(self, snap):
        from kubernetes_tpu.models.batch_solver import solve
        if self.foreign is None:
            foreign = mk_fault_pod(0, prefix="foreign-")
            foreign.spec.containers[0].resources.limits["cpu"] = \
                Quantity("32")
            foreign.spec.host = foreign.status.host = "n000"
            self.foreign = foreign
            self._factory.scheduled_pods.add(foreign)
        return solve(snap)


CAS_VICTIM = "p00005"


@pytest.mark.parametrize("fault", ["cas_lost_bind", "store_delta_mid_solve",
                                   "gang_in_the_wave", "solve_raises_once"])
def test_loop_under_fault_commits_the_serial_oracles_decisions(
        fault, monkeypatch):
    gangs = fault == "gang_in_the_wave"
    pods = mk_gang_pods() if gangs else \
        [mk_fault_pod(i) for i in range(384)]
    wave_size = 32 if gangs else 128
    m = Master()
    client = Client(InProcessTransport(m))
    for i in range(N_FAULT_NODES):
        client.nodes().create(mk_node(f"n{i:03d}", cpu="64", mem="256Gi"))
    for p in pods:
        client.pods().create(p)
    factory = ConfigFactory(client)
    factory.backoff = PodBackoff(initial=0.05, max_duration=0.2)
    config = factory.create()
    binder = None
    bind_lost = set()        # pods whose next bind will be rejected
    if fault == "cas_lost_bind":
        binder = config.binder = _FailOnceBinder(config.binder, CAS_VICTIM)
        bind_lost.add(CAS_VICTIM)
    # the whole backlog and node set synced before the first drain
    assert _wait(lambda: len(factory.pod_queue.list()) >= len(pods)
                 and len(factory.node_store.list()) >= N_FAULT_NODES, 30.0)
    sched = BatchScheduler(config, factory, client, wave_size=wave_size,
                           wave_linger_s=0.02)
    solver = None
    if fault == "store_delta_mid_solve":
        solver = sched.solver = _InjectingSolver(factory)
    raised = []
    if fault == "solve_raises_once":
        # the first wave is encoded (and its snapshot never applied),
        # handed whole to the error handler, and comes back
        from kubernetes_tpu.scheduler import tpu_batch
        real_solve = tpu_batch.solve

        def solve_raising_once(snap, **kw):
            if not raised:
                raised.append(len(snap.pod_names))
                raise RuntimeError("injected solve failure")
            return real_solve(snap, **kw)

        monkeypatch.setattr(tpu_batch, "solve", solve_raising_once)
    waves = []
    inner = sched._default_solve

    def recording(nodes, existing, pending, services, tctx=None):
        decisions = inner(nodes, existing, pending, services, tctx=tctx)
        waves.append((list(nodes), list(pending), list(decisions.hosts)))
        return decisions

    sched._default_solve = recording
    sched.run()
    try:
        assert _wait(lambda: all(p.spec.host
                                 for p in client.pods().list().items), 90.0)
    finally:
        sched.stop()
        factory.stop()
    final = {p.metadata.name: p.spec.host
             for p in client.pods().list().items}

    existing = []
    placed_in = {}           # pod name -> waves that placed it
    for k, (nodes, pending, hosts) in enumerate(waves):
        assert hosts == solve_serial(nodes, existing, pending,
                                     gangs=gangs), f"wave {k}"
        if k == 0 and solver is not None:
            existing.append(solver.foreign)
        for pod, host in zip(pending, hosts):
            if host is None:
                continue
            name = pod.metadata.name
            placed_in.setdefault(name, []).append(k)
            if name in bind_lost:
                bind_lost.discard(name)     # this bind was the one rejected
                continue
            bound = deep_clone(pod)
            bound.spec.host = bound.status.host = host
            existing.append(bound)
    # nothing is bound twice: one placing wave a pod, but for the pod
    # whose first bind was lost, which came back and bound in a later wave
    for name, ks in placed_in.items():
        retried = binder is not None and name == CAS_VICTIM
        assert len(ks) == 1 + retried, (name, ks)
    assert {p.metadata.name: p.spec.host for p in existing
            if p is not getattr(solver, "foreign", None)} == final
    if binder is not None:
        assert binder.failed == 1
        first, second = placed_in[CAS_VICTIM]
        assert second > first
    if solver is not None:
        assert len(waves) >= 2      # a wave was solved past the delta
    if fault == "solve_raises_once":
        assert len(raised) == 1 and raised[0] >= wave_size
    if gangs:
        by_group = {}
        for name, host in final.items():
            by_group.setdefault(int(name[1:]) // 4, []).append(host)
        assert all(len(h) == 4 and all(h) for h in by_group.values())


def test_batch_scheduler_holds_a_gang_below_quorum_until_it_is_whole():
    """A wave dropped at the gate: three members of a gang of four are
    failed back to the queue wave after wave, and bind together once the
    fourth arrives."""
    m = Master()
    client = Client(InProcessTransport(m))
    for i in range(2):
        client.nodes().create(mk_node(f"n{i}"))
    factory = ConfigFactory(client)
    factory.backoff = PodBackoff(initial=0.05, max_duration=0.2)
    config = factory.create()
    sched = BatchScheduler(config, factory, client, wave_size=8,
                           wave_linger_s=0.05).run()

    def member(i):
        p = mk_pod(f"m{i}")
        p.metadata.annotations = {
            gang_mod.GANG_NAME_ANNOTATION: "quartet",
            gang_mod.GANG_MIN_MEMBERS_ANNOTATION: "4"}
        return p

    try:
        for i in range(3):
            client.pods().create(member(i))
        time.sleep(0.5)
        assert not any(p.spec.host for p in client.pods().list().items)
        client.pods().create(member(3))
        assert _wait(lambda: all(p.spec.host
                                 for p in client.pods().list().items))
        assert len(client.pods().list().items) == 4
    finally:
        sched.stop()
        factory.stop()
