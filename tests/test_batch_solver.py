"""TPU batch solver vs serial oracle — bit-identical equivalence.

The decision contract (BASELINE.md north star): for every snapshot, the batch
solver's per-pod host choices equal the serial reference path's, including
tie-breaks. Fuzzed over cluster shapes, resources, ports, selectors, PDs,
pinned hosts, and service spreading groups.
"""

import random

import numpy as np
import pytest

from kubernetes_tpu.api import types as api
from kubernetes_tpu.api.quantity import Quantity
from kubernetes_tpu.models.batch_solver import decisions_to_names, solve
from kubernetes_tpu.models.oracle import solve_serial
from kubernetes_tpu.models.snapshot import encode_snapshot


def mk_node(name, cpu_m=4000, mem=8 << 30, labels=None, extra=None):
    cap = {"cpu": Quantity(f"{cpu_m}m"), "memory": Quantity(mem)}
    for k, v in (extra or {}).items():
        cap[k] = Quantity(v)
    return api.Node(
        metadata=api.ObjectMeta(name=name, labels=labels or {}),
        spec=api.NodeSpec(capacity=cap))


def mk_pod(name, ns="default", cpu_m=0, mem=0, host="", labels=None,
           node_selector=None, host_ports=(), pds=(), extra=None):
    limits = {}
    if cpu_m:
        limits["cpu"] = Quantity(f"{cpu_m}m")
    if mem:
        limits["memory"] = Quantity(mem)
    for k, v in (extra or {}).items():
        limits[k] = Quantity(v)
    return api.Pod(
        metadata=api.ObjectMeta(name=name, namespace=ns, uid=f"uid-{ns}-{name}",
                                labels=labels or {}),
        spec=api.PodSpec(
            host=host,
            node_selector=node_selector or {},
            containers=[api.Container(
                name="c", image="i",
                ports=[api.ContainerPort(container_port=80 + i, host_port=p)
                       for i, p in enumerate(host_ports)],
                resources=api.ResourceRequirements(limits=limits))],
            volumes=[api.Volume(name=f"v{i}", source=api.VolumeSource(
                gce_persistent_disk=api.GCEPersistentDiskVolumeSource(pd_name=pd)))
                for i, pd in enumerate(pds)]),
        status=api.PodStatus(host=host))


def assert_equivalent(nodes, existing, pending, services=()):
    serial = solve_serial(nodes, existing, pending, services)
    snap = encode_snapshot(nodes, existing, pending, services)
    chosen, _ = solve(snap)
    batch = decisions_to_names(snap, chosen)
    assert batch == serial, (
        f"divergence:\n  serial={serial}\n  batch ={batch}")
    return serial


# -- targeted cases ---------------------------------------------------------

def test_empty_cluster():
    assert solve_serial([], [], [mk_pod("p")]) == [None]
    snap = encode_snapshot([mk_node("n1")], [], [])
    chosen, _ = solve(snap)
    assert chosen.shape == (0,)


def test_least_requested_prefers_idle():
    nodes = [mk_node("busy"), mk_node("idle")]
    existing = [mk_pod("e", cpu_m=3000, mem=6 << 30, host="busy")]
    hosts = assert_equivalent(nodes, existing, [mk_pod("x", cpu_m=500, mem=1 << 30)])
    assert hosts == ["idle"]


def test_sequential_commits_affect_later_pods():
    """Each decision must update usage for the next — the serial semantics."""
    nodes = [mk_node("a", cpu_m=1000, mem=1 << 30), mk_node("b", cpu_m=1000, mem=1 << 30)]
    pending = [mk_pod(f"p{i}", cpu_m=600, mem=100 << 20) for i in range(3)]
    hosts = assert_equivalent(nodes, [], pending)
    assert hosts[0] != hosts[1]        # second pod forced to the other node
    assert hosts[2] is None            # third fits nowhere


def test_capacity_exhaustion_and_unschedulable():
    nodes = [mk_node("n", cpu_m=1000, mem=1 << 30)]
    pending = [mk_pod("big", cpu_m=2000), mk_pod("ok", cpu_m=500),
               mk_pod("overflow", cpu_m=600)]
    hosts = assert_equivalent(nodes, [], pending)
    assert hosts == [None, "n", None]


def test_zero_request_always_fits():
    nodes = [mk_node("full", cpu_m=100, mem=1 << 20)]
    existing = [mk_pod("hog", cpu_m=100, mem=1 << 20, host="full")]
    hosts = assert_equivalent(nodes, existing, [mk_pod("zero")])
    assert hosts == ["full"]


def test_zero_capacity_never_constrains():
    n = api.Node(metadata=api.ObjectMeta(name="limitless"), spec=api.NodeSpec(capacity={}))
    hosts = assert_equivalent([n], [], [mk_pod("huge", cpu_m=10**6, mem=1 << 40)])
    assert hosts == ["limitless"]


def test_host_port_conflicts_within_wave():
    nodes = [mk_node("a"), mk_node("b")]
    pending = [mk_pod(f"p{i}", host_ports=(8080,)) for i in range(3)]
    hosts = assert_equivalent(nodes, [], pending)
    assert sorted(h for h in hosts if h) == ["a", "b"]
    assert hosts.count(None) == 1


def test_node_selector_and_pinned_host():
    nodes = [mk_node("gpu", labels={"accel": "tpu"}), mk_node("plain")]
    pending = [
        mk_pod("wants-accel", node_selector={"accel": "tpu"}),
        mk_pod("pinned", host="plain"),
        mk_pod("pinned-unknown", host="ghost"),
    ]
    hosts = assert_equivalent(nodes, [], pending)
    assert hosts == ["gpu", "plain", None]


def test_pd_conflicts_within_wave_and_snapshot():
    nodes = [mk_node("a"), mk_node("b")]
    existing = [mk_pod("e", host="a", pds=("disk-1",))]
    pending = [mk_pod("p1", pds=("disk-1",)), mk_pod("p2", pds=("disk-1",))]
    hosts = assert_equivalent(nodes, existing, pending)
    assert hosts == ["b", None]


def test_service_spreading_within_wave():
    nodes = [mk_node(f"n{i}") for i in range(4)]
    svc = api.Service(metadata=api.ObjectMeta(name="web", namespace="default"),
                      spec=api.ServiceSpec(port=80, selector={"app": "web"}))
    pending = [mk_pod(f"w{i}", labels={"app": "web"}) for i in range(8)]
    hosts = assert_equivalent(nodes, [], pending, [svc])
    placement = {n: hosts.count(n) for n in ("n0", "n1", "n2", "n3")}
    assert set(placement.values()) == {2}  # perfect spread


def test_spreading_counts_unassigned_peers():
    """Unassigned peers (status.host == '') count toward maxCount
    (spreading.go:62-68) — slot N in the group counts."""
    nodes = [mk_node("n0"), mk_node("n1")]
    svc = api.Service(metadata=api.ObjectMeta(name="s", namespace="default"),
                      spec=api.ServiceSpec(port=80, selector={"app": "x"}))
    existing = [mk_pod("floating", labels={"app": "x"}, host="")]
    assert_equivalent(nodes, existing, [mk_pod("p", labels={"app": "x"})], [svc])


def test_tie_break_matches_oracle():
    nodes = [mk_node(f"n{i}") for i in range(7)]
    pending = [mk_pod(f"p{i}") for i in range(7)]  # all scores equal
    hosts = assert_equivalent(nodes, [], pending)
    assert len(set(hosts)) > 1  # hash tie-break spreads across nodes


def test_multiple_namespaces_and_services():
    nodes = [mk_node(f"n{i}") for i in range(3)]
    svcs = [
        api.Service(metadata=api.ObjectMeta(name="a", namespace="ns1"),
                    spec=api.ServiceSpec(port=80, selector={"app": "a"})),
        api.Service(metadata=api.ObjectMeta(name="b", namespace="ns2"),
                    spec=api.ServiceSpec(port=80, selector={"app": "b"})),
    ]
    pending = [mk_pod("a1", ns="ns1", labels={"app": "a"}),
               mk_pod("b1", ns="ns2", labels={"app": "b"}),
               mk_pod("a2", ns="ns1", labels={"app": "a"}),
               mk_pod("c", ns="ns1")]
    assert_equivalent(nodes, [], pending, svcs)


# -- fuzz -------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(12))
def test_fuzz_equivalence(seed):
    rng = random.Random(seed)
    n_nodes = rng.randint(1, 16)
    n_existing = rng.randint(0, 20)
    n_pending = rng.randint(1, 40)
    zones = ["z1", "z2", "z3"]
    nodes = []
    for i in range(n_nodes):
        labels = {}
        if rng.random() < 0.5:
            labels["zone"] = rng.choice(zones)
        if rng.random() < 0.3:
            labels["disk"] = "ssd"
        nodes.append(mk_node(
            f"n{i}", cpu_m=rng.choice([500, 1000, 2000, 4000]),
            mem=rng.choice([1 << 30, 2 << 30, 8 << 30]), labels=labels))
    services = [
        api.Service(metadata=api.ObjectMeta(name="svc-a", namespace="default"),
                    spec=api.ServiceSpec(port=80, selector={"app": "a"})),
        api.Service(metadata=api.ObjectMeta(name="svc-b", namespace="default"),
                    spec=api.ServiceSpec(port=80, selector={"app": "b"})),
    ]

    def random_pod(name, may_have_host):
        kw = dict(
            cpu_m=rng.choice([0, 100, 250, 500, 1000]),
            mem=rng.choice([0, 64 << 20, 512 << 20, 1 << 30]),
            labels={"app": rng.choice(["a", "b", "c"])} if rng.random() < 0.7 else {},
        )
        if rng.random() < 0.3:
            kw["host_ports"] = (rng.choice([8080, 9090]),)
        if rng.random() < 0.2:
            kw["node_selector"] = {"zone": rng.choice(zones)}
        if rng.random() < 0.15:
            kw["pds"] = (rng.choice(["pd1", "pd2"]),)
        if may_have_host:
            kw["host"] = rng.choice([n.metadata.name for n in nodes]
                                    + ["", "dead-node"])
        return mk_pod(name, **kw)

    existing = [random_pod(f"e{i}", True) for i in range(n_existing)]
    pending = [random_pod(f"p{i}", False) for i in range(n_pending)]
    assert_equivalent(nodes, existing, pending, services)


# -- R-dimensional resources (BASELINE config 3: 3 resource dimensions) -----

def test_third_resource_dimension_constrains():
    """A GPU dimension advertised by some nodes: pods requesting GPUs only
    fit where capacity remains; the solver and serial oracle agree."""
    nodes = [mk_node("gpu0", extra={"nvidia.com/gpu": 2}),
             mk_node("gpu1", extra={"nvidia.com/gpu": 1}),
             mk_node("plain")]
    pending = [mk_pod(f"g{i}", cpu_m=100, mem=64 << 20,
                      extra={"nvidia.com/gpu": 1}) for i in range(4)]
    serial = assert_equivalent(nodes, [], pending)
    # 3 GPUs exist in total; the 4th pod must fail
    assert sorted(h for h in serial if h) == ["gpu0", "gpu0", "gpu1"]
    assert serial.count(None) == 1


def test_extra_dimension_changes_least_requested_average():
    """With R=3 the LeastRequested average divides by 3 (sum // R); nodes
    advertising idle extra capacity score differently than an R=2 encode
    would. Equivalence must hold — both paths use the same universe."""
    nodes = [mk_node("a", cpu_m=1000, mem=1 << 30,
                     extra={"ephemeral-storage": 100 << 30}),
             mk_node("b", cpu_m=1000, mem=1 << 30)]
    existing = [mk_pod("e0", cpu_m=500, mem=512 << 20, host="a"),
                mk_pod("e1", cpu_m=100, mem=64 << 20, host="b")]
    pending = [mk_pod(f"p{i}", cpu_m=100, mem=64 << 20,
                      extra={"ephemeral-storage": 10 << 30} if i % 2 else None)
               for i in range(6)]
    assert_equivalent(nodes, existing, pending)


def test_request_only_resource_is_unschedulable():
    """An extended resource no node advertises cannot be satisfied: the
    requesting pods fail everywhere (strict dim_fits semantics), while
    zero-request pods keep the reference fast path."""
    nodes = [mk_node("n0"), mk_node("n1")]
    pending = [mk_pod("p0", extra={"fpga": 4}),          # request-only dim
               mk_pod("p1", cpu_m=100, extra={"fpga": 1}),
               mk_pod("p2")]                             # requests nothing
    serial = assert_equivalent(nodes, [], pending)
    assert serial[0] is None and serial[1] is None and serial[2] is not None


def test_zero_quantity_advertisement_widens_divisor():
    """A node advertising {'nvidia.com/gpu': 0} (e.g. drained device
    plugin) still widens the serial LeastRequested universe — the divisor
    counts advertised NAMES, not nonzero capacities. Regression for the
    solver deriving adv_extra from cap != 0."""
    nodes = [mk_node("drained", extra={"nvidia.com/gpu": 0}),
             mk_node("a"), mk_node("b", cpu_m=2000)]
    existing = [mk_pod("e0", cpu_m=1000, mem=2 << 30, host="a")]
    pending = [mk_pod(f"p{i}", cpu_m=500, mem=512 << 20) for i in range(4)]
    assert_equivalent(nodes, existing, pending)


def test_least_requested_divisor_follows_filtered_nodes():
    """The serial path prioritizes over the FILTERED node list, so its
    LeastRequested universe — and divisor — shrinks when the only node
    advertising an extra dim is filtered out. Regression: the solver must
    derive the divisor per pod from the feasible nodes, not the wave."""
    nodes = [mk_node("gpu", extra={"nvidia.com/gpu": 2}),
             mk_node("a"), mk_node("b", cpu_m=2000)]
    # the gpu node is knocked out by a port conflict, not resources
    existing = [mk_pod("holder", host="gpu", host_ports=(8080,))]
    pending = [mk_pod(f"p{i}", cpu_m=500, mem=512 << 20, host_ports=(8080,))
               for i in range(3)]
    serial = assert_equivalent(nodes, existing, pending)
    assert "gpu" not in serial


@pytest.mark.parametrize("seed", range(6))
def test_fuzz_equivalence_r_dimensional(seed):
    """Fuzz with a third + fourth resource dimension in the mix."""
    rng = random.Random(1000 + seed)
    nodes = []
    for i in range(rng.randint(2, 10)):
        extra = {}
        if rng.random() < 0.6:
            extra["nvidia.com/gpu"] = rng.choice([1, 2, 4])
        if rng.random() < 0.4:
            extra["ephemeral-storage"] = rng.choice([50 << 30, 200 << 30])
        nodes.append(mk_node(f"n{i}", cpu_m=rng.choice([1000, 2000, 4000]),
                             mem=rng.choice([2 << 30, 8 << 30]), extra=extra))
    def rpod(name, may_have_host):
        extra = {}
        if rng.random() < 0.4:
            extra["nvidia.com/gpu"] = rng.choice([1, 2])
        if rng.random() < 0.3:
            extra["ephemeral-storage"] = rng.choice([10 << 30, 40 << 30])
        kw = dict(cpu_m=rng.choice([0, 100, 500]),
                  mem=rng.choice([0, 64 << 20, 1 << 30]), extra=extra)
        if may_have_host:
            kw["host"] = rng.choice([n.metadata.name for n in nodes] + [""])
        return mk_pod(name, **kw)
    existing = [rpod(f"e{i}", True) for i in range(rng.randint(0, 15))]
    pending = [rpod(f"p{i}", False) for i in range(rng.randint(1, 30))]
    assert_equivalent(nodes, existing, pending)


def test_packed_transfer_is_bit_identical(monkeypatch):
    """KTPU_PACK_TRANSFER=on ships the whole SolverInputs tree as ONE
    uint8 buffer re-materialized on device by jitted bitcasts (one
    transfer per wave instead of ~32); decisions and scores must be
    bit-identical to the per-array transfer path across dtype variety
    (int32/int64 planes, bool masks, uint32 bitmask words, float32
    zone one-hots)."""

    import bench
    from kubernetes_tpu.models.batch_solver import solve
    from kubernetes_tpu.models.snapshot import encode_snapshot

    for kw in ({}, {"three_resources": True},
               {"gang_groups": 6, "gang_size": 8}):
        n_pods = 0 if kw.get("gang_groups") else 120
        nodes, existing, pending, services = bench.build_cluster(
            40, n_pods, **kw)
        snap = encode_snapshot(nodes, existing, pending, services)
        monkeypatch.setenv("KTPU_PACK_TRANSFER", "on")
        cp, sp = solve(snap)
        monkeypatch.setenv("KTPU_PACK_TRANSFER", "off")
        cd, sd = solve(snap)
        assert np.array_equal(np.asarray(cp), np.asarray(cd)), kw
        assert np.array_equal(np.asarray(sp), np.asarray(sd)), kw


# -- host-vs-device wave router ---------------------------------------------

class TestWaveRouter:
    """The measured small-wave dispatch (batch_solver.WaveRouter). On the
    CPU-only test backend there is no second device, so auto mode must
    degrade to the device plan; the calibration machinery is exercised by
    pointing the router's CPU seam at the default device."""

    def _host(self):
        from kubernetes_tpu.models.batch_solver import (
            peer_bound_of, snapshot_to_host_inputs)
        nodes = [mk_node(f"n{i}") for i in range(8)]
        pending = [mk_pod(f"p{i}", cpu_m=100) for i in range(16)]
        snap = encode_snapshot(nodes, [], pending, [])
        return (snap, snapshot_to_host_inputs(snap), snap.policy,
                snap.has_gangs, peer_bound_of(snap))

    def test_auto_without_second_backend_is_device(self, monkeypatch):
        from kubernetes_tpu.models import batch_solver as bs
        monkeypatch.setenv("KTPU_WAVE_ROUTER", "auto")
        _, host, pol, gangs, pb = self._host()
        plan = bs.WaveRouter().plan_for(host, pol, gangs, pb)
        assert plan.path == "device" and plan.device is None

    def test_off_and_bad_mode(self, monkeypatch):
        from kubernetes_tpu.models import batch_solver as bs
        _, host, pol, gangs, pb = self._host()
        monkeypatch.setenv("KTPU_WAVE_ROUTER", "off")
        assert bs.WaveRouter().plan_for(host, pol, gangs, pb).path == "device"
        monkeypatch.setenv("KTPU_WAVE_ROUTER", "bogus")
        monkeypatch.setattr(bs, "_host_cpu_device",
                            lambda: __import__("jax").devices()[0])
        with pytest.raises(ValueError):
            bs.WaveRouter().plan_for(host, pol, gangs, pb)

    def test_calibration_measures_both_and_caches(self, monkeypatch):
        import jax

        from kubernetes_tpu.models import batch_solver as bs
        monkeypatch.setenv("KTPU_WAVE_ROUTER", "auto")
        monkeypatch.setattr(bs, "_host_cpu_device",
                            lambda: jax.devices()[0])
        router = bs.WaveRouter()
        snap, host, pol, gangs, pb = self._host()
        plan = router.plan_for(host, pol, gangs, pb)
        assert plan.path in ("host", "device")
        assert plan.host_s == plan.host_s          # calibration ran
        assert plan.device_s == plan.device_s
        assert router.plan_for(host, pol, gangs, pb) is plan  # cached
        # decisions via the routed pipeline match the serial oracle
        inp = bs.ship_inputs(host, plan.device)
        chosen, _ = bs.solve_device(inp, pol, gangs, pb,
                                    force_scan=plan.device is not None)
        nodes = [mk_node(f"n{i}") for i in range(8)]
        pending = [mk_pod(f"p{i}", cpu_m=100) for i in range(16)]
        assert decisions_to_names(snap, np.asarray(chosen)) == \
            solve_serial(nodes, [], pending, [])

    def test_big_wave_skips_host_calibration(self, monkeypatch):
        import jax

        from kubernetes_tpu.models import batch_solver as bs
        monkeypatch.setenv("KTPU_WAVE_ROUTER", "auto")
        monkeypatch.setattr(bs, "_host_cpu_device",
                            lambda: jax.devices()[0])
        monkeypatch.setattr(bs, "_ROUTER_MAX_HOST_CELLS", 4)
        _, host, pol, gangs, pb = self._host()
        plan = bs.WaveRouter().plan_for(host, pol, gangs, pb)
        assert plan.path == "device"
        assert plan.host_s != plan.host_s          # no calibration paid


# -- the _ktpu_rows derived-row cache ---------------------------------------

class TestEncodeRowCacheDebug:
    def test_debug_mode_catches_in_place_spec_mutation(self, monkeypatch):
        """KTPU_DEBUG recomputes every cache hit: mutating a PodSpec in
        place (instead of going through deep_clone, which drops the
        cache) must fail loudly instead of silently encoding stale rows."""
        from kubernetes_tpu.models import snapshot as snapshot_mod
        monkeypatch.setattr(snapshot_mod, "_DEBUG_VERIFY_ROWS", True)
        nodes = [mk_node("n0")]
        pod = mk_pod("p0", cpu_m=100)
        encode_snapshot(nodes, [], [pod], [])        # populates the cache
        encode_snapshot(nodes, [], [pod], [])        # verified hit: fine
        pod.spec.containers[0].resources.limits["cpu"] = Quantity("2")
        with pytest.raises(AssertionError, match="_ktpu_rows cache stale"):
            encode_snapshot(nodes, [], [pod], [])

    def test_deep_clone_drops_the_cache(self):
        from kubernetes_tpu.runtime.clone import deep_clone
        nodes = [mk_node("n0")]
        pod = mk_pod("p1", cpu_m=100)
        encode_snapshot(nodes, [], [pod], [])
        assert "_ktpu_rows" in pod.spec.__dict__
        clone = deep_clone(pod)
        assert "_ktpu_rows" not in clone.spec.__dict__
