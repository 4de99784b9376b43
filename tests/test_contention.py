"""Wave contention — concurrent schedulers racing over one store.

SURVEY §7 hard part (e): when multiple schedulers (or one scheduler's
waves against a churning store) land binds concurrently, the Binding CAS
(set spec.host iff empty — registry/resources.BindingREST, ref:
pkg/registry/pod/etcd/etcd.go:98-152) must guarantee every pod binds
EXACTLY once, losers requeue with backoff, and no wave deadlocks — even
with injected CAS conflicts and stale node/pod stores.
"""

import time


from kubernetes_tpu.api import types as api
from kubernetes_tpu.api.quantity import Quantity
from kubernetes_tpu.apiserver.master import Master, MasterConfig
from kubernetes_tpu.client.client import Client, InProcessTransport
from kubernetes_tpu.scheduler.driver import ConfigFactory, Scheduler
from kubernetes_tpu.scheduler.tpu_batch import BatchScheduler
from kubernetes_tpu.storage.memstore import ErrCASConflict, MemStore


def mk_node(name, cpu="16", mem="64Gi"):
    return api.Node(metadata=api.ObjectMeta(name=name),
                    spec=api.NodeSpec(capacity={"cpu": Quantity(cpu),
                                                "memory": Quantity(mem)}))


def mk_pod(name, cpu_m=100):
    return api.Pod(
        metadata=api.ObjectMeta(name=name, namespace="default"),
        spec=api.PodSpec(containers=[api.Container(
            name="c", image="i",
            resources=api.ResourceRequirements(limits={
                "cpu": Quantity(f"{cpu_m}m"),
                "memory": Quantity("64Mi")}))]))


def wait_for(pred, timeout=30.0, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


def start_batch(master, wave_size=16, linger=0.05):
    client = Client(InProcessTransport(master))
    factory = ConfigFactory(client)
    config = factory.create()
    sched = BatchScheduler(config, factory, client, wave_size=wave_size,
                           wave_linger_s=linger).run()
    return sched, factory


def start_serial(master):
    client = Client(InProcessTransport(master))
    factory = ConfigFactory(client)
    config = factory.create()
    sched = Scheduler(config).run()
    return sched, factory


def all_bound(client, n):
    pods = client.pods().list().items
    return len(pods) == n and all(p.spec.host for p in pods)


def test_two_batch_schedulers_bind_every_pod_exactly_once():
    """Both schedulers see every unassigned pod (their reflectors watch the
    same store); the Binding CAS picks one winner per pod, the loser
    requeues and drops it after the refetch. Nothing double-binds, nothing
    starves."""
    m = Master()
    admin = Client(InProcessTransport(m))
    for i in range(4):
        admin.nodes().create(mk_node(f"n{i}"))
    s1, f1 = start_batch(m)
    s2, f2 = start_batch(m)
    try:
        time.sleep(0.3)
        for i in range(48):
            admin.pods().create(mk_pod(f"p{i:03d}"))
        assert wait_for(lambda: all_bound(admin, 48)), \
            "contended pods never all bound"
        hosts = {p.metadata.name: p.spec.host
                 for p in admin.pods().list().items}
        assert all(h.startswith("n") for h in hosts.values())
        # stability: nobody rebinds an already-bound pod (CAS would 409)
        time.sleep(0.3)
        hosts2 = {p.metadata.name: p.spec.host
                  for p in admin.pods().list().items}
        assert hosts == hosts2
    finally:
        s1.stop(); s2.stop(); f1.stop(); f2.stop()


def test_serial_and_batch_scheduler_race():
    m = Master()
    admin = Client(InProcessTransport(m))
    for i in range(3):
        admin.nodes().create(mk_node(f"n{i}"))
    sb, fb = start_batch(m)
    ss, fs = start_serial(m)
    try:
        time.sleep(0.3)
        for i in range(30):
            admin.pods().create(mk_pod(f"mix{i:03d}"))
        assert wait_for(lambda: all_bound(admin, 30)), \
            "mixed-scheduler pods never all bound"
    finally:
        sb.stop(); ss.stop(); fb.stop(); fs.stop()


def test_injected_binding_cas_conflicts_requeue_and_converge():
    """Forced CAS conflicts on the bind path: the wave hands the pod to the
    error handler (backoff + refetch + requeue) and a later wave binds it."""
    store = MemStore()
    m = Master(MasterConfig(store=store))
    admin = Client(InProcessTransport(m))
    admin.nodes().create(mk_node("n0"))
    # every pod's first two bind attempts lose the CAS race
    for i in range(6):
        store.inject_error("compare_and_swap",
                           f"/registry/pods/default/cas{i}",
                           ErrCASConflict("injected bind race"), times=2)
    sched, factory = start_batch(m, wave_size=8, linger=0.02)
    try:
        time.sleep(0.3)
        for i in range(6):
            admin.pods().create(mk_pod(f"cas{i}"))
        assert wait_for(lambda: all_bound(admin, 6), timeout=45.0), \
            "pods behind injected CAS conflicts never bound"
    finally:
        sched.stop(); factory.stop()


def test_wave_against_stale_node_store_converges():
    """A wave solved against a node set containing a just-deleted node may
    emit bindings for it; the system must converge — pods bound to the
    dead node are not our concern (node controller evicts them), but pods
    NOT yet bound must keep scheduling onto surviving nodes, and waves
    must not wedge."""
    m = Master()
    admin = Client(InProcessTransport(m))
    for i in range(3):
        admin.nodes().create(mk_node(f"n{i}", cpu="2"))
    sched, factory = start_batch(m, wave_size=8, linger=0.1)
    try:
        time.sleep(0.3)  # node store synced with 3 nodes
        # delete a node; the poller refreshes every 0.1s but the first
        # wave may still see it
        admin.nodes().delete("n2")
        for i in range(12):
            admin.pods().create(mk_pod(f"st{i:02d}", cpu_m=300))
        assert wait_for(lambda: all_bound(admin, 12), timeout=45.0), \
            "pods never converged after node deletion mid-wave"
        # eventually-consistent: after the poller caught up, later binds
        # must only target live nodes; allow early ones on n2
        live = {p.spec.host for p in admin.pods().list().items}
        assert live <= {"n0", "n1", "n2"}
        # capacity proof that survivors carried the load: 12x300m needs
        # more than one 2-cpu node
        assert len(live & {"n0", "n1"}) == 2
    finally:
        sched.stop(); factory.stop()


def test_concurrent_waves_with_churning_deletes():
    """Pods deleted while queued or mid-wave must not wedge the scheduler:
    the error handler's refetch drops vanished pods."""
    m = Master()
    admin = Client(InProcessTransport(m))
    admin.nodes().create(mk_node("n0"))
    sched, factory = start_batch(m, wave_size=4, linger=0.1)
    try:
        time.sleep(0.3)
        for i in range(20):
            admin.pods().create(mk_pod(f"ch{i:02d}"))
        # delete half while waves are in flight
        for i in range(0, 20, 2):
            try:
                admin.pods().delete(f"ch{i:02d}")
            except Exception:
                pass  # already bound+running is fine too
        def survivors_bound():
            pods = admin.pods().list().items
            return all(p.spec.host for p in pods)
        assert wait_for(survivors_bound, timeout=45.0), \
            "survivor pods never bound amid churn deletes"
    finally:
        sched.stop(); factory.stop()


def test_batched_bindings_transactional_commit():
    """The bindings batch endpoint: one store pass, per-item CAS results."""
    m = Master()
    admin = Client(InProcessTransport(m))
    admin.nodes().create(mk_node("n0"))
    for i in range(4):
        admin.pods().create(mk_pod(f"b{i}"))
    # pre-bind b2 so its slot conflicts
    admin.pods().bind(api.Binding(
        metadata=api.ObjectMeta(name="b2", namespace="default"),
        pod_name="b2", host="n0"))
    blist = api.BindingList(items=[
        api.Binding(metadata=api.ObjectMeta(name=f"b{i}",
                                            namespace="default"),
                    pod_name=f"b{i}", host="n0")
        for i in range(4)] + [
        api.Binding(metadata=api.ObjectMeta(name="ghost",
                                            namespace="default"),
                    pod_name="ghost", host="n0"),
        api.Binding(metadata=api.ObjectMeta(namespace="default"))])
    results = admin.pods().bind_many(blist)
    by_name = {r.pod_name: r for r in results.items}
    assert by_name["b0"].error == "" and by_name["b1"].error == ""
    assert by_name["b3"].error == ""
    assert "already assigned" in by_name["b2"].error
    assert by_name["ghost"].code == 404
    assert by_name[""].code == 400
    # winners really bound
    for i in (0, 1, 3):
        assert admin.pods().get(f"b{i}").spec.host == "n0"


def test_two_batch_schedulers_race_batched_binds():
    """Both schedulers commit whole waves through the batched CAS: still
    exactly-once binding under contention."""
    m = Master()
    admin = Client(InProcessTransport(m))
    for i in range(4):
        admin.nodes().create(mk_node(f"n{i}"))
    s1, f1 = start_batch(m, wave_size=32, linger=0.02)
    s2, f2 = start_batch(m, wave_size=32, linger=0.02)
    try:
        time.sleep(0.3)
        for i in range(64):
            admin.pods().create(mk_pod(f"bb{i:03d}"))
        assert wait_for(lambda: all_bound(admin, 64), timeout=45.0)
        hosts = {p.metadata.name: p.spec.host
                 for p in admin.pods().list().items}
        time.sleep(0.3)
        hosts2 = {p.metadata.name: p.spec.host
                  for p in admin.pods().list().items}
        assert hosts == hosts2
    finally:
        s1.stop(); s2.stop(); f1.stop(); f2.stop()


def test_batched_bindings_reject_cross_namespace_items():
    """Items naming another namespace are refused per-item: authz and
    admission ran against the request namespace only."""
    m = Master()
    admin = Client(InProcessTransport(m))
    admin.nodes().create(mk_node("n0"))
    admin.pods().create(mk_pod("same-ns"))
    blist = api.BindingList(items=[
        api.Binding(metadata=api.ObjectMeta(name="same-ns",
                                            namespace="default"),
                    pod_name="same-ns", host="n0"),
        api.Binding(metadata=api.ObjectMeta(name="sneaky",
                                            namespace="victim"),
                    pod_name="sneaky", host="n0")])
    results = admin.pods().bind_many(blist)
    by_name = {r.pod_name: r for r in results.items}
    assert by_name["same-ns"].error == ""
    assert by_name["sneaky"].code == 403
    assert "does not match request namespace" in by_name["sneaky"].error
