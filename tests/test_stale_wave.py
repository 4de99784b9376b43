"""Stale-wave double-bind: two scheduler instances race one node's
capacity; the kubelet's node-side re-admission catches the overcommit.

ref: the reference re-checks ports/selector/capacity on the node
(handleNotFittingPods, pkg/kubelet/kubelet.go:1750-1772) precisely
because the scheduler's view can be stale — with batched waves the race
window is a whole wave, so this drives it end-to-end through the live
HTTP stack: apiserver + two BatchSchedulers (the second frozen on a
stale snapshot) + a real Kubelet admission pass writing PodFailed back.

Also pins the CAS-loser semantics at wave granularity: the stale
scheduler re-binding an already-bound pod loses the BindingREST CAS
(ref: pkg/registry/pod/etcd/etcd.go:125-127) and its error handler must
NOT requeue the pod (it re-fetches and sees it scheduled, ref:
factory.go makeDefaultErrorFunc), while a genuinely unschedulable pod
IS requeued with backoff.
"""

import time

import pytest

from kubernetes_tpu.api import types as api
from kubernetes_tpu.api.quantity import Quantity
from kubernetes_tpu.apiserver.http import APIServer
from kubernetes_tpu.apiserver.master import Master
from kubernetes_tpu.client.client import Client
from kubernetes_tpu.client.http import HTTPTransport
from kubernetes_tpu.kubelet.kubelet import Kubelet
from kubernetes_tpu.kubelet.runtime import FakeRuntime
from kubernetes_tpu.scheduler.driver import ConfigFactory
from kubernetes_tpu.scheduler.tpu_batch import BatchScheduler


def mk_pod(name, mcpu):
    return api.Pod(
        metadata=api.ObjectMeta(name=name, namespace="default"),
        spec=api.PodSpec(containers=[api.Container(
            name="c", image="img",
            resources=api.ResourceRequirements(
                limits={"cpu": Quantity(f"{mcpu}m")}))]))


@pytest.fixture()
def stack():
    srv = APIServer(Master()).start()
    client = Client(HTTPTransport(srv.base_url))
    client.nodes().create(api.Node(
        metadata=api.ObjectMeta(name="node-1"),
        spec=api.NodeSpec(capacity={"cpu": Quantity("1"),
                                    "memory": Quantity("4Gi")})))
    yield srv, client
    srv.stop()


def wait_for(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.05)
    return False


def mk_sched(client):
    factory = ConfigFactory(client)
    config = factory.create()
    return factory, BatchScheduler(config, factory, client,
                                   wave_linger_s=0.05)


def test_stale_wave_overcommit_rejected_by_kubelet_readmission(stack):
    srv, client = stack
    client.pods().create(mk_pod("p1", 600))
    client.pods().create(mk_pod("p2", 600))

    fa, sa = mk_sched(client)
    fb, sb = mk_sched(client)
    try:
        # both schedulers converge on the SAME view: two pending pods,
        # one empty 1-cpu node
        assert wait_for(lambda: len(fa.pod_queue.list()) == 2
                        and len(fa.node_store.list()) == 1)
        assert wait_for(lambda: len(fb.pod_queue.list()) == 2
                        and len(fb.node_store.list()) == 1)

        # freeze B on that snapshot DETERMINISTICALLY: stop + JOIN the
        # reflector threads, so no in-flight watch delivery (e.g. A's
        # bind of p1, below) can land in B's stores afterwards — without
        # the join, B could observe the bind, correctly refuse p2 for
        # capacity, and break the staleness premise. Then steer B's wave
        # to p2 by draining p1 from its queue.
        assert fb.stop(join=True), "reflector threads did not stop in time"
        drained = fb.pod_queue.pop(timeout=1.0)
        assert drained.metadata.name == "p1"

        # wave A: drains [p1, p2]; capacity fits only one 600m pod, so A
        # binds p1 and hands p2 to the error handler (backoff + requeue)
        bound_a = sa.schedule_wave(timeout=1.0)
        assert bound_a == 1
        assert client.pods().get("p1").spec.host == "node-1"
        # the unschedulable pod is REQUEUED (factory error handler)
        assert wait_for(lambda: any(
            p.metadata.name == "p2" for p in fa.pod_queue.list()), 5.0)

        # wave B (stale): believes node-1 is empty, binds p2 there — the
        # apiserver accepts (p2's host CAS is clean); node now overcommitted
        bound_b = sb.schedule_wave(timeout=1.0)
        assert bound_b == 1
        assert client.pods().get("p2").spec.host == "node-1"

        # the kubelet's wave-granularity re-admission: one sync pass over
        # what the node now sees; the overflow pod fails node-side
        kubelet = Kubelet("node-1", FakeRuntime("node-1"), client=client,
                          volume_mgr=None)
        assigned = client.pods().list(field_selector="spec.host=node-1").items
        assert {p.metadata.name for p in assigned} == {"p1", "p2"}
        kubelet.sync_pods(assigned)
        kubelet.pod_workers.wait_idle(10.0)

        def phases():
            return {p.metadata.name: p.status.phase
                    for p in client.pods().list().items}

        assert wait_for(lambda: phases().get("p2") == api.PodFailed, 10.0), \
            phases()
        failed = client.pods().get("p2")
        assert "capacity" in failed.status.message.lower()
        # the fitting pod was admitted and runs
        assert phases().get("p1") != api.PodFailed
        assert any("p1" in r.name for r in kubelet.runtime.list_containers())
        kubelet.stop()
    finally:
        fa.stop()
        fb.stop()
        sa.stop()
        sb.stop()


def test_cas_loser_is_not_requeued_when_pod_already_scheduled(stack):
    srv, client = stack
    client.pods().create(mk_pod("q1", 100))

    fa, sa = mk_sched(client)
    fb, sb = mk_sched(client)
    try:
        assert wait_for(lambda: len(fa.pod_queue.list()) == 1
                        and len(fa.node_store.list()) == 1)
        assert wait_for(lambda: len(fb.pod_queue.list()) == 1
                        and len(fb.node_store.list()) == 1)
        # snapshot B's stale view of q1 BEFORE the bind; stop+join freezes
        # the stores deterministically, and the stale pod is re-injected
        # below in case the drain landed before the join
        stale_q1 = fb.pod_queue.list()[0]
        assert fb.stop(join=True), "reflector threads did not stop in time"

        assert sa.schedule_wave(timeout=1.0) == 1
        assert client.pods().get("q1").spec.host == "node-1"
        fb.pod_queue.add(stale_q1)  # B still believes q1 is pending

        # stale B re-binds q1 -> BindingREST CAS rejects (409); the error
        # handler re-fetches, sees it scheduled, and must NOT requeue
        assert sb.schedule_wave(timeout=1.0) == 0
        time.sleep(0.3)
        assert all(p.metadata.name != "q1" for p in fb.pod_queue.list())
        assert client.pods().get("q1").spec.host == "node-1"  # unchanged
    finally:
        fa.stop()
        fb.stop()
        sa.stop()
        sb.stop()
