"""Scheduler tests.

Table-driven predicate/priority tests mirroring the reference
(pkg/scheduler/predicates_test.go, priorities_test.go, spreading_test.go),
generic-scheduler tests, and driver tests with a mock binder
(plugin/pkg/scheduler/scheduler_test.go).
"""

import time

import pytest

from kubernetes_tpu.api import types as api
from kubernetes_tpu.api.quantity import Quantity
from kubernetes_tpu.scheduler import predicates as preds
from kubernetes_tpu.scheduler import priorities as prios
from kubernetes_tpu.scheduler import plugins as schedplugins
from kubernetes_tpu.scheduler.driver import (
    ConfigFactory,
    PodBackoff,
    Scheduler,
    SimpleModeler,
    filter_schedulable_nodes,
)
from kubernetes_tpu.scheduler.generic import (
    FitError,
    GenericScheduler,
    select_host_deterministic,
)
from kubernetes_tpu.scheduler.listers import (
    FakeMinionLister,
    FakeNodeInfo,
    FakePodLister,
    FakeServiceLister,
)
from kubernetes_tpu.scheduler.priorities import HostPriority


def mk_pod(name="p", ns="default", cpu=None, mem=None, host="", labels=None,
           node_selector=None, host_ports=(), pd=None):
    containers = [api.Container(
        name="c", image="i",
        ports=[api.ContainerPort(container_port=80 + i, host_port=p)
               for i, p in enumerate(host_ports)],
        resources=api.ResourceRequirements(limits={
            k: v for k, v in
            (("cpu", Quantity(cpu) if cpu else None),
             ("memory", Quantity(mem) if mem else None)) if v is not None}))]
    volumes = []
    if pd:
        volumes.append(api.Volume(name="v", source=api.VolumeSource(
            gce_persistent_disk=api.GCEPersistentDiskVolumeSource(pd_name=pd))))
    return api.Pod(
        metadata=api.ObjectMeta(name=name, namespace=ns, labels=labels or {},
                                uid=f"uid-{ns}-{name}"),
        spec=api.PodSpec(containers=containers, host=host, volumes=volumes,
                         node_selector=node_selector or {}),
        status=api.PodStatus(host=host))


def mk_node(name, cpu="4", mem="8Gi", labels=None, conditions=None):
    return api.Node(
        metadata=api.ObjectMeta(name=name, labels=labels or {}),
        spec=api.NodeSpec(capacity={"cpu": Quantity(cpu), "memory": Quantity(mem)}),
        status=api.NodeStatus(conditions=conditions or []))


# -- predicates (table-driven, ref: predicates_test.go) ---------------------

def test_pod_fits_resources():
    node = mk_node("n1", cpu="1", mem="1Gi")
    fit = preds.ResourceFit(FakeNodeInfo(api.NodeList(items=[node])))
    existing = [mk_pod("e1", cpu="500m", mem="512Mi", host="n1")]
    assert fit.pod_fits_resources(mk_pod("x", cpu="400m", mem="256Mi"), existing, "n1")
    assert not fit.pod_fits_resources(mk_pod("x", cpu="600m"), existing, "n1")
    assert not fit.pod_fits_resources(mk_pod("x", mem="600Mi"), existing, "n1")
    # zero-request pods always fit (predicates.go:129)
    assert fit.pod_fits_resources(mk_pod("x"), existing, "n1")
    # zero capacity dimension never constrains (predicates.go:106-108)
    node0 = mk_node("n0")
    node0.spec.capacity = {}
    fit0 = preds.ResourceFit(FakeNodeInfo(api.NodeList(items=[node0])))
    assert fit0.pod_fits_resources(mk_pod("x", cpu="100", mem="100Gi"), [], "n0")


def test_pod_fits_ports():
    assert preds.pod_fits_ports(mk_pod("x", host_ports=(8080,)), [], "n1")
    existing = [mk_pod("e", host_ports=(8080,))]
    assert not preds.pod_fits_ports(mk_pod("x", host_ports=(8080,)), existing, "n1")
    assert preds.pod_fits_ports(mk_pod("x", host_ports=(8081,)), existing, "n1")
    # port 0 never conflicts
    assert preds.pod_fits_ports(mk_pod("x", host_ports=(0,)),
                                [mk_pod("e", host_ports=(0,))], "n1")


def test_no_disk_conflict():
    existing = [mk_pod("e", pd="disk-1")]
    assert not preds.no_disk_conflict(mk_pod("x", pd="disk-1"), existing, "n1")
    assert preds.no_disk_conflict(mk_pod("x", pd="disk-2"), existing, "n1")
    assert preds.no_disk_conflict(mk_pod("x"), existing, "n1")


def test_match_node_selector():
    node = mk_node("n1", labels={"zone": "us-east", "disk": "ssd"})
    sel = preds.NodeSelector(FakeNodeInfo(api.NodeList(items=[node])))
    assert sel.pod_selector_matches(mk_pod("x", node_selector={"zone": "us-east"}), [], "n1")
    assert not sel.pod_selector_matches(mk_pod("x", node_selector={"zone": "eu"}), [], "n1")
    assert sel.pod_selector_matches(mk_pod("x"), [], "n1")


def test_pod_fits_host():
    assert preds.pod_fits_host(mk_pod("x", host=""), [], "n1")
    p = mk_pod("x")
    p.spec.host = "n1"
    assert preds.pod_fits_host(p, [], "n1")
    assert not preds.pod_fits_host(p, [], "n2")


def test_node_label_presence():
    node = mk_node("n1", labels={"zone": "a", "retiring": "2015"})
    info = FakeNodeInfo(api.NodeList(items=[node]))
    require = preds.NodeLabelChecker(info, ["zone"], presence=True)
    assert require.check_node_label_presence(mk_pod("x"), [], "n1")
    forbid = preds.NodeLabelChecker(info, ["retiring"], presence=False)
    assert not forbid.check_node_label_presence(mk_pod("x"), [], "n1")


def test_service_affinity():
    nodes = api.NodeList(items=[mk_node("n1", labels={"zone": "z1"}),
                                mk_node("n2", labels={"zone": "z2"})])
    info = FakeNodeInfo(nodes)
    svc = api.Service(metadata=api.ObjectMeta(name="s", namespace="default"),
                      spec=api.ServiceSpec(port=80, selector={"app": "web"}))
    peer = mk_pod("peer", labels={"app": "web"}, host="n1")
    aff = preds.ServiceAffinity(FakePodLister([peer]), FakeServiceLister([svc]),
                                info, ["zone"])
    new_pod = mk_pod("new", labels={"app": "web"})
    # peer is in z1 -> only z1 nodes fit
    assert aff.check_service_affinity(new_pod, [], "n1")
    assert not aff.check_service_affinity(new_pod, [], "n2")
    # no peers -> all nodes fit
    lonely = preds.ServiceAffinity(FakePodLister([]), FakeServiceLister([svc]),
                                   info, ["zone"])
    assert lonely.check_service_affinity(new_pod, [], "n2")


# -- priorities (ref: priorities_test.go) -----------------------------------

def test_calculate_score_go_semantics():
    assert prios.calculate_score(0, 0, "n") == 0       # zero capacity
    assert prios.calculate_score(11, 10, "n") == 0     # over capacity
    assert prios.calculate_score(0, 10, "n") == 10
    assert prios.calculate_score(5, 10, "n") == 5
    assert prios.calculate_score(1, 3, "n") == 6       # (2*10)//3, Go truncation


def test_least_requested_priority():
    nodes = api.NodeList(items=[mk_node("busy", cpu="1", mem="1Gi"),
                                mk_node("idle", cpu="1", mem="1Gi")])
    existing = [mk_pod("e", cpu="500m", mem="512Mi", host="busy")]
    pod = mk_pod("x", cpu="100m", mem="128Mi")
    got = prios.least_requested_priority(pod, FakePodLister(existing),
                                         FakeMinionLister(nodes))
    scores = {hp.host: hp.score for hp in got}
    assert scores["idle"] > scores["busy"]
    # exact values: busy cpu (1000-600)*10//1000=4 mem (1024-640)*10//1024=3 -> 3
    assert scores["busy"] == (4 + 3) // 2
    # idle cpu (1000-100)*10//1000=9, mem (1024-128)*10//1024=8 -> 8
    assert scores["idle"] == (9 + 8) // 2


def test_service_spreading_priority():
    nodes = api.NodeList(items=[mk_node("n1"), mk_node("n2"), mk_node("n3")])
    svc = api.Service(metadata=api.ObjectMeta(name="s", namespace="default"),
                      spec=api.ServiceSpec(port=80, selector={"app": "web"}))
    peers = [mk_pod("a", labels={"app": "web"}, host="n1"),
             mk_pod("b", labels={"app": "web"}, host="n1"),
             mk_pod("c", labels={"app": "web"}, host="n2")]
    spread = prios.ServiceSpread(FakeServiceLister([svc]))
    got = spread.calculate_spread_priority(
        mk_pod("new", labels={"app": "web"}), FakePodLister(peers),
        FakeMinionLister(nodes))
    scores = {hp.host: hp.score for hp in got}
    assert scores == {"n1": 0, "n2": 5, "n3": 10}


def test_service_anti_affinity_zone_spread():
    nodes = api.NodeList(items=[
        mk_node("n1", labels={"zone": "z1"}),
        mk_node("n2", labels={"zone": "z2"}),
        mk_node("n3", labels={}),
    ])
    svc = api.Service(metadata=api.ObjectMeta(name="s", namespace="default"),
                      spec=api.ServiceSpec(port=80, selector={"app": "web"}))
    peers = [mk_pod("a", labels={"app": "web"}, host="n1")]
    anti = prios.ServiceAntiAffinity(FakeServiceLister([svc]), "zone")
    got = anti.calculate_anti_affinity_priority(
        mk_pod("new", labels={"app": "web"}), FakePodLister(peers),
        FakeMinionLister(nodes))
    scores = {hp.host: hp.score for hp in got}
    assert scores["n1"] == 0     # zone z1 has the peer
    assert scores["n2"] == 10    # empty zone
    assert scores["n3"] == 0     # unlabeled nodes score 0


def test_equal_priority_and_node_label_priority():
    nodes = api.NodeList(items=[mk_node("n1", labels={"gpu": "yes"}), mk_node("n2")])
    got = prios.equal_priority(mk_pod("x"), FakePodLister([]), FakeMinionLister(nodes))
    assert all(hp.score == 1 for hp in got)
    pri = prios.NodeLabelPrioritizer("gpu", presence=True)
    got = pri.calculate_node_label_priority(mk_pod("x"), FakePodLister([]),
                                            FakeMinionLister(nodes))
    assert {hp.host: hp.score for hp in got} == {"n1": 10, "n2": 0}


# -- generic scheduler ------------------------------------------------------

def _default_scheduler(nodes, pods, services=()):
    args = schedplugins.PluginFactoryArgs(
        pod_lister=FakePodLister(list(pods)),
        service_lister=FakeServiceLister(list(services)),
        node_lister=FakeMinionLister(nodes),
        node_info=FakeNodeInfo(nodes))
    keys = schedplugins.get_algorithm_provider(schedplugins.DEFAULT_PROVIDER)
    return GenericScheduler(
        schedplugins.get_predicates(keys["predicates"], args),
        schedplugins.get_priorities(keys["priorities"], args),
        args.pod_lister)


def test_schedule_picks_least_requested():
    nodes = api.NodeList(items=[mk_node("busy"), mk_node("idle")])
    existing = [mk_pod("e", cpu="3", mem="6Gi", host="busy")]
    s = _default_scheduler(nodes, existing)
    assert s.schedule(mk_pod("x", cpu="1", mem="1Gi"), FakeMinionLister(nodes)) == "idle"


def test_schedule_respects_predicates():
    nodes = api.NodeList(items=[mk_node("small", cpu="1", mem="1Gi"),
                                mk_node("big", cpu="8", mem="16Gi")])
    s = _default_scheduler(nodes, [])
    assert s.schedule(mk_pod("x", cpu="4", mem="4Gi"), FakeMinionLister(nodes)) == "big"


def test_schedule_no_fit_raises_fit_error():
    nodes = api.NodeList(items=[mk_node("n1", cpu="1", mem="1Gi")])
    s = _default_scheduler(nodes, [])
    with pytest.raises(FitError) as ei:
        s.schedule(mk_pod("x", cpu="10"), FakeMinionLister(nodes))
    assert "PodFitsResources" in str(ei.value)


def test_schedule_no_nodes():
    s = _default_scheduler(api.NodeList(), [])
    with pytest.raises(FitError):
        s.schedule(mk_pod("x"), FakeMinionLister(api.NodeList()))


def test_select_host_deterministic_and_spreading():
    pl = [HostPriority("a", 5), HostPriority("b", 5), HostPriority("c", 3)]
    h1 = select_host_deterministic(pl, "pod-1")
    assert h1 == select_host_deterministic(pl, "pod-1")  # reproducible
    assert h1 in ("a", "b")
    # different pods spread across the tied best hosts
    chosen = {select_host_deterministic(pl, f"pod-{i}") for i in range(32)}
    assert chosen == {"a", "b"}


def test_schedule_deterministic_across_runs():
    nodes = api.NodeList(items=[mk_node(f"n{i}") for i in range(8)])
    s = _default_scheduler(nodes, [])
    pod = mk_pod("x", cpu="1", mem="1Gi")
    first = s.schedule(pod, FakeMinionLister(nodes))
    for _ in range(5):
        assert s.schedule(pod, FakeMinionLister(nodes)) == first


# -- policy config ----------------------------------------------------------

def test_policy_round_trip():
    policy_json = """
    {"kind": "Policy", "apiVersion": "v1",
     "predicates": [
        {"name": "PodFitsPorts"},
        {"name": "ZoneAffinity", "argument": {"serviceAffinity": {"labels": ["zone"]}}},
        {"name": "RequireRegion", "argument": {"labelsPresence": {"labels": ["region"], "presence": true}}}
     ],
     "priorities": [
        {"name": "LeastRequestedPriority", "weight": 2},
        {"name": "ZoneSpread", "weight": 1, "argument": {"serviceAntiAffinity": {"label": "zone"}}},
        {"name": "PreferGPU", "weight": 3, "argument": {"labelPreference": {"label": "gpu", "presence": true}}}
     ]}
    """
    policy = schedplugins.load_policy(policy_json)
    assert [p.name for p in policy.predicates] == ["PodFitsPorts", "ZoneAffinity", "RequireRegion"]
    assert policy.predicates[1].service_affinity_labels == ["zone"]
    assert policy.priorities[0].weight == 2
    nodes = api.NodeList(items=[mk_node("n1", labels={"zone": "z", "region": "r"})])
    args = schedplugins.PluginFactoryArgs(
        pod_lister=FakePodLister([]), service_lister=FakeServiceLister([]),
        node_lister=FakeMinionLister(nodes), node_info=FakeNodeInfo(nodes))
    pred_map = schedplugins.predicates_from_policy(policy, args)
    # Schedulable is structural (kubectl cordon), injected regardless of
    # the policy vocabulary
    assert set(pred_map) == {"PodFitsPorts", "ZoneAffinity", "RequireRegion",
                             "Schedulable"}
    prio_list = schedplugins.priorities_from_policy(policy, args)
    assert [c.weight for c in prio_list] == [2, 1, 3]


# -- driver -----------------------------------------------------------------

def test_backoff_doubles_and_caps():
    t = [0.0]
    b = PodBackoff(initial=1.0, max_duration=8.0, clock=lambda: t[0])
    assert [b.get_backoff("k") for _ in range(5)] == [1.0, 2.0, 4.0, 8.0, 8.0]
    t[0] = 100.0
    b.gc(max_age=60)
    assert b.get_backoff("k") == 1.0  # entry gc'd, starts over


def test_filter_schedulable_nodes():
    ready = mk_node("ready", conditions=[api.NodeCondition(type="Ready", status="True")])
    not_ready = mk_node("notready", conditions=[api.NodeCondition(type="Ready", status="False")])
    cordoned = mk_node("cordoned", conditions=[
        api.NodeCondition(type="Schedulable", status="False"),
        api.NodeCondition(type="Ready", status="True")])
    reachable = mk_node("reachable", conditions=[
        api.NodeCondition(type="Reachable", status="True")])
    bare = mk_node("bare")
    out = filter_schedulable_nodes(api.NodeList(
        items=[ready, not_ready, cordoned, reachable, bare]))
    assert [n.metadata.name for n in out.items] == ["ready", "reachable", "bare"]


class _RecordingBinder:
    def __init__(self, fail_times=0):
        self.bindings = []
        self.fail_times = fail_times

    def bind(self, binding):
        if self.fail_times > 0:
            self.fail_times -= 1
            raise RuntimeError("injected bind failure")
        self.bindings.append(binding)


def test_schedule_one_binds_and_assumes():
    """ref: scheduler_test.go TestScheduler."""
    from kubernetes_tpu.client.cache import FIFO, Store
    from kubernetes_tpu.scheduler.driver import SchedulerConfig

    nodes = api.NodeList(items=[mk_node("n1")])
    queue = FIFO()
    modeler = SimpleModeler(queue, Store())
    binder = _RecordingBinder()
    pod = mk_pod("x", cpu="1", mem="1Gi")
    errors_seen = []

    config = SchedulerConfig(
        modeler=modeler,
        minion_lister=FakeMinionLister(nodes),
        algorithm=_default_scheduler(nodes, []),
        binder=binder,
        next_pod=lambda timeout=None: pod,
        error=lambda p, e: errors_seen.append((p, e)),
    )
    dest = Scheduler(config).schedule_one()
    assert dest == "n1"
    assert binder.bindings[0].pod_name == "x"
    assert binder.bindings[0].host == "n1"
    assert not errors_seen
    # assumed pod visible through the modeler's lister with its host set
    assumed = modeler.list()
    assert assumed and assumed[0].spec.host == "n1"


def test_schedule_one_bind_failure_calls_error():
    from kubernetes_tpu.client.cache import FIFO, Store
    from kubernetes_tpu.scheduler.driver import SchedulerConfig

    nodes = api.NodeList(items=[mk_node("n1")])
    errors_seen = []
    config = SchedulerConfig(
        modeler=SimpleModeler(FIFO(), Store()),
        minion_lister=FakeMinionLister(nodes),
        algorithm=_default_scheduler(nodes, []),
        binder=_RecordingBinder(fail_times=1),
        next_pod=lambda timeout=None: mk_pod("x"),
        error=lambda p, e: errors_seen.append(e),
    )
    assert Scheduler(config).schedule_one() is None
    assert len(errors_seen) == 1


def test_modeler_prunes_on_confirmation():
    from kubernetes_tpu.client.cache import FIFO, Store

    queue, scheduled = FIFO(), Store()
    modeler = SimpleModeler(queue, scheduled)
    pod = mk_pod("x", host="n1")
    modeler.assume_pod(pod)
    assert len(modeler.list()) == 1
    scheduled.add(pod)  # watch confirms the bind
    assert len(modeler.list()) == 1  # still listed once (from scheduled)
    assert len(modeler.assumed.list()) == 0  # but no longer assumed


# -- end-to-end against the real master -------------------------------------

def test_scheduler_against_master():
    """The full loop: reflectors + FIFO + algorithm + binding write."""
    from kubernetes_tpu.apiserver.master import Master
    from kubernetes_tpu.client.client import Client, InProcessTransport

    m = Master()
    client = Client(InProcessTransport(m))
    for i in range(3):
        client.nodes().create(mk_node(f"n{i}"))
    factory = ConfigFactory(client)
    config = factory.create()
    sched = Scheduler(config).run()
    try:
        for i in range(5):
            client.pods().create(mk_pod(f"p{i}", cpu="100m", mem="64Mi"))
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            pods = client.pods().list().items
            if all(p.spec.host for p in pods) and len(pods) == 5:
                break
            time.sleep(0.05)
        pods = client.pods().list().items
        assert len(pods) == 5
        assert all(p.spec.host.startswith("n") for p in pods), [p.spec.host for p in pods]
    finally:
        sched.stop()
        factory.stop()


def test_scheduler_retries_when_no_fit():
    """A pod too big for the cluster schedules after capacity appears."""
    from kubernetes_tpu.apiserver.master import Master
    from kubernetes_tpu.client.client import Client, InProcessTransport

    m = Master()
    client = Client(InProcessTransport(m))
    client.nodes().create(mk_node("small", cpu="1", mem="1Gi"))
    factory = ConfigFactory(client)
    factory.backoff = PodBackoff(initial=0.05, max_duration=0.2)
    config = factory.create()
    sched = Scheduler(config).run()
    try:
        client.pods().create(mk_pod("big", cpu="4", mem="4Gi"))
        time.sleep(0.3)
        assert client.pods().get("big").spec.host == ""  # cannot fit yet
        client.nodes().create(mk_node("huge", cpu="16", mem="32Gi"))
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if client.pods().get("big").spec.host == "huge":
                break
            time.sleep(0.05)
        assert client.pods().get("big").spec.host == "huge"
    finally:
        sched.stop()
        factory.stop()
