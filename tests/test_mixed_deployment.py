"""``sched-mixed-5000n`` (benchmarks/configs/) on the CPU: the file as the
harness reads it — upstream's ``SchedulingNodeAffinity``: one node shape,
every node labelled with the one zone, every pod pinned to it — and its own
templates cut to 60 nodes and sent wave after wave down the path the wave
loop's ``_solve_snap`` takes: ``IncrementalEncoder.encode_delta``, the
resident planes, ``batch_solver.solve``. Host and score have to equal the
plain reference's (``benchmarks/references/serial_default.py``, which
imports nothing of the program) pod by pod.

On that cluster every node matches the selector, so the predicate costs
what it costs and decides nothing. ``WHOLE_PROVIDER`` below is therefore
this file's own seeded deployment — no configuration, named by no source —
in which MatchNodeSelector, PodFitsPorts and ServiceSpreading all decide:
three pools, seven kinds of pod with selectors, two host ports and five
services. The same path is held to the same reference on it; a selector, a
port or a service dropped on either side has to show; and the encoder's
count of the pods that carry one has to be what the wave holds. Last, the
prewarm's fill trigger, which measures a port vocabulary in ports."""

import collections
import copy
import json
import os
import random
import time

import pytest

from benchmarks.harness import deployment as dep
from benchmarks.references import serial_default as ref
from kubernetes_tpu.api import types as api
from kubernetes_tpu.api.quantity import Quantity
from kubernetes_tpu.models import batch_solver as bs
from kubernetes_tpu.models import incremental
from kubernetes_tpu.models import resident as rs
from kubernetes_tpu.solver.prewarm import PrewarmController
from kubernetes_tpu.solver.service import _dims_of
from kubernetes_tpu.util import metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ZONE = "topology.kubernetes.io/zone"
TEMPLATE = "pod-with-node-affinity"


def _pod(name, weight, cpu, memory, **more):
    return dict(name=name, weight=weight,
                limits={"cpu": cpu, "memory": memory}, **more)


WHOLE_PROVIDER = {
    "namespace": "default",
    "nodes": 60,
    "node_templates": [
        {"name": "pool-a", "count": 36, "labels": {"zone": "a"},
         "capacity": {"cpu": "4", "memory": "32Gi"}},
        {"name": "pool-b", "count": 18, "labels": {"zone": "b"},
         "capacity": {"cpu": "8", "memory": "64Gi"}},
        {"name": "pool-c", "count": 6, "labels": {"zone": "c"},
         "capacity": {"cpu": "16", "memory": "128Gi"}}],
    "pod_templates": [
        _pod("batch", 6, "100m", "500Mi"),
        _pod("web", 5, "250m", "1Gi", labels={"app": "web"}),
        _pod("api", 3, "500m", "2Gi", labels={"app": "api"},
             node_selector={"zone": "b"}),
        _pod("db", 2, "1", "4Gi", labels={"app": "db"},
             node_selector={"zone": "c"}),
        _pod("cache", 2, "1", "8Gi", labels={"app": "cache"}),
        _pod("edge", 1, "500m", "1Gi", labels={"app": "edge"},
             host_ports=[8080]),
        _pod("agent", 1, "100m", "200Mi", host_ports=[9100])],
    "services": [{"name": n, "selector": {"app": n}}
                 for n in ("web", "api", "db", "cache", "edge")],
}
WHOLE_NAMES = [t["name"] for t in WHOLE_PROVIDER["pod_templates"]]


def _config(nodes=None):
    """The configuration's file, cut to ``nodes`` nodes."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "sched-mixed-5000n.json")) as f:
        config = json.load(f)
    if nodes:
        config["nodes"] = config["node_templates"][0]["count"] = nodes
    return config


def _whole(pools=None):
    """``WHOLE_PROVIDER`` with its three pools cut to ``pools`` nodes."""
    config = copy.deepcopy(WHOLE_PROVIDER)
    if pools:
        config["nodes"] = sum(pools)
        for template, count in zip(config["node_templates"], pools):
            template["count"] = count
    return config


def _api_node(name, t):
    return api.Node(
        metadata=api.ObjectMeta(name=name, labels=dict(t["labels"])),
        spec=api.NodeSpec(capacity={k: Quantity(v)
                                    for k, v in t["capacity"].items()}))


def _api_pod(uid, t):
    return api.Pod(
        metadata=api.ObjectMeta(name=uid, namespace=t["namespace"], uid=uid,
                                labels=dict(t["labels"])),
        spec=api.PodSpec(
            node_selector=dict(t["node_selector"]),
            containers=[api.Container(
                name=t["container"], image=t["image"],
                ports=[api.ContainerPort(host_port=p, container_port=p)
                       for p in t["host_ports"]],
                resources=api.ResourceRequirements(limits={
                    k: Quantity(v) for k, v in t["limits"].items()}))]))


def _api_services(services):
    return [api.Service(
        metadata=api.ObjectMeta(name=s["name"], namespace=s["namespace"]),
        spec=api.ServiceSpec(port=80, selector=dict(s["selector"])))
        for s in services]


def _waves(templates, seed, count):
    """``count`` pods in the plan's order (every template in proportion to
    its weight, shuffled from the seed), cut into waves of mixed sizes."""
    rng = random.Random(seed)
    plan = dep.pod_plan(templates, "window", seed, count)[:count]
    pods = [(f"uid-{seed}-{i:04d}", templates[t]) for i, t in enumerate(plan)]
    waves, at = [], 0
    while at < len(pods):
        size = rng.choice([1, 3, 8, 20, 50])
        waves.append(pods[at:at + size])
        at += size
    return waves


class WarmPath:
    """The program's side: one encoder and one set of resident planes for
    the run, each wave through ``encode_delta`` with the binds of the wave
    before as its upserts."""

    def __init__(self, nodes, services):
        self.nodes = [_api_node(n, t) for n, t in sorted(nodes.items())]
        self.services = _api_services(services)
        self.enc = incremental.IncrementalEncoder()
        self.planes = rs.ResidentPlanes()
        self.bound, self.upserted = [], []

    def wave(self, wave):
        pending = [_api_pod(uid, t) for uid, t in wave]
        snap = self.enc.encode_delta(self.nodes, self.upserted, [], pending,
                                     self.services)
        if snap is None:               # the first wave: nothing is held yet
            snap = self.enc.encode(self.nodes, self.bound, pending,
                                   self.services)
        self.upserted = []
        host = self.planes.host_inputs(snap)
        chosen, scores = bs.solve(snap, host=host, resident=self.planes)
        hosts = bs.decisions_to_names(snap, chosen)
        for pod, h in zip(pending, hosts):
            if h is not None:
                pod.spec.host = pod.status.host = h
                self.bound.append(pod)
                self.upserted.append(pod)
        return [(h, int(s) if h is not None else -1)
                for h, s in zip(hosts, scores)]


def _run(seed, make, count, program=None, reference=None):
    """-> (pods whose host or score differ, templates seen bound, pods the
    control places elsewhere). ``make()`` gives a deployment; ``program`` /
    ``reference`` edit that side's copy of it first."""
    sides = []
    for edit in (program, reference):
        config = make()
        if edit:
            edit(config)
        sides.append((dep.nodes_of(config), dep.pod_templates(config),
                      dep.services(config)))
    (p_nodes, p_templates, p_services), (r_nodes, r_templates, r_services) \
        = sides
    warm = WarmPath(p_nodes, p_services)
    cluster = ref.Cluster(r_nodes, r_services)
    control = ref.Cluster(r_nodes, r_services)
    differ = elsewhere = 0
    seen = collections.Counter()
    for p_wave, r_wave in zip(_waves(p_templates, seed, count),
                              _waves(r_templates, seed, count)):
        got = warm.wave(p_wave)
        want = ref.solve_wave(cluster, r_wave)
        differ += sum(g != w for g, w in zip(got, want))
        elsewhere += sum(
            c[0] != w[0] for c, w in
            zip(ref.solve_wave_uncommitted(control, r_wave), want))
        seen.update(t["name"] for (_uid, t), (h, _s) in zip(p_wave, got)
                    if h is not None)
    return differ, seen, elsewhere


DEPLOYMENTS = {"configuration": (lambda: _config(60), [TEMPLATE]),
               "whole_provider": (_whole, WHOLE_NAMES)}


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("deployment", sorted(DEPLOYMENTS))
def test_the_warm_path_decides_host_and_score_as_serial_default_does(
        deployment, seed):
    make, names = DEPLOYMENTS[deployment]
    differ, seen, elsewhere = _run(2 ** 31 + seed, make, 240)
    assert differ == 0
    assert set(seen) == set(names)
    assert sum(seen.values()) == 240          # the cluster holds them all
    assert elsewhere >= 24         # the control: the in-wave commit put off


def _no_selectors(config):
    for t in config["pod_templates"]:
        t["node_selector"] = {}


def _no_ports(config):
    for t in config["pod_templates"]:
        t["host_ports"] = []


def _no_services(config):
    config["services"] = []


@pytest.mark.parametrize("side", ["program", "reference"])
@pytest.mark.parametrize("edit", [_no_selectors, _no_ports, _no_services])
def test_a_selector_a_port_or_a_service_dropped_on_one_side_shows(
        edit, side):
    """Ten nodes and 200 pods: more holders of a port than nodes, the
    pinned pools full before the end, peers on every node."""
    def make():
        return _whole((6, 3, 1))

    differ, _seen, _ = _run(2 ** 31 + 77, make, 200, **{side: edit})
    assert differ >= 1
    assert _run(2 ** 31 + 77, make, 200)[0] == 0


def _no_node_labels(config):
    for t in config["node_templates"]:
        t["labels"] = {}


@pytest.mark.parametrize("side", ["program", "reference"])
def test_the_configuration_s_node_label_dropped_on_one_side_shows(side):
    """The file's own templates: without the zone on the nodes that side
    binds nothing, so every pod differs."""
    differ, seen, _ = _run(2 ** 31 + 78, lambda: _config(10), 60,
                           **{side: _no_node_labels})
    assert differ == 60
    assert sum(seen.values()) == (0 if side == "program" else 60)


def test_the_configuration_s_selector_decides_where_zones_differ():
    """Half the nodes moved to another zone, on both sides: the two agree
    and every pod sits in the zone it names."""
    def make():
        config = _config(20)
        [pool] = config["node_templates"]
        config["node_templates"] = [
            dict(pool, name="zone2", count=10, labels={ZONE: "zone2"}),
            dict(pool, name="zone1", count=10)]
        return config

    nodes = dep.nodes_of(make())
    warm = WarmPath(nodes, [])
    cluster = ref.Cluster(nodes, [])
    for wave in _waves(dep.pod_templates(make()), 2 ** 31 + 79, 120):
        got = warm.wave(wave)
        assert got == ref.solve_wave(cluster, wave)
        assert all("node-00010" <= host <= "node-00019" for host, _s in got)


# -- the file, as the harness and the reference read it -----------------------

def test_the_file_states_the_suite_s_node_affinity_case_and_nothing_else():
    config = _config()
    [pool] = dep.node_templates(config)
    assert pool["count"] == config["nodes"] == 5000
    assert pool["capacity"] == {"cpu": "4", "memory": "32Gi"}
    assert pool["labels"] == {ZONE: "zone1"}
    [pod] = dep.pod_templates(config)
    assert pod["name"] == TEMPLATE and pod["in"] == ["warm", "window"]
    assert pod["limits"] == {"cpu": "100m", "memory": "500Mi"}
    assert pod["node_selector"] == {ZONE: "zone1"}
    assert pod["labels"] == {} and pod["host_ports"] == []
    assert pod["namespace"] == "default"
    assert dep.services(config) == []
    assert "#SchedulingNodeAffinity/" in config["source"]
    assert config["reference"] == "serial_default"
    assert config["kernel_program"] == "pallas"
    assert config["reduced"] == ["measured_pods", "init_pods"]
    for key in ("name", "sizes", "node_label",
                "node_affinity_as_node_selector", "headroom"):
        assert config["assumed"][key]


def test_the_file_differs_from_its_control_by_the_label_and_the_selector():
    """``sched-basic-5000n`` is the cell's control: the same node and pod
    shapes, scheduler, pins and cuts."""
    config = _config()
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "sched-basic-5000n.json")) as f:
        basic = json.load(f)
    [pool], [pod] = dep.node_templates(config), dep.pod_templates(config)
    [b_pool], [b_pod] = dep.node_templates(basic), dep.pod_templates(basic)
    assert dict(pool, labels={}, name="") == dict(b_pool, name="")
    assert dict(pod, node_selector={}, name="") == dict(b_pod, name="")
    for key in ("nodes", "namespace", "scheduler", "env", "kernel_program",
                "reduced", "measured_pods"):
        assert config[key] == basic[key], key


def test_serial_default_takes_the_whole_deployment_without_raising():
    config = _config()
    cluster = ref.Cluster(dep.nodes_of(config), dep.services(config))
    [pod] = dep.pod_templates(config)
    decided = ref.solve_wave(cluster, [(f"uid-{i}", pod) for i in range(7)])
    assert len({host for host, _score in decided}) == 7
    # the headroom the file writes down: cpu is the tighter limit
    cpu = Quantity(pod["limits"]["cpu"]).milli_value()
    assert 5000 * (4000 // cpu) == 200_000


# -- the encoder's account of what a wave's pods carry ------------------------

def _part(name):
    parts = metrics.wave_parts()
    return parts.count(name), parts.sum(name)


def test_the_counter_and_the_span_read_what_a_seeded_wave_holds():
    config = _whole()
    templates = dep.pod_templates(config)
    warm = WarmPath(dep.nodes_of(config), dep.services(config))
    [first, second] = [
        [(f"uid-c{w}-{i:03d}", templates[t]) for i, t in enumerate(
            dep.pod_plan(templates, "window", 5 + w, 40)[:40])]
        for w in (0, 1)]
    counted = incremental.constrained_pods()
    for wave in (first, second):          # a cold wave, then a delta wave
        plain = sum(t["name"] == "batch" for _uid, t in wave)
        assert 0 < plain < len(wave)
        before, span0 = counted.value(), _part("encode.pods")
        warm.wave(wave)
        assert counted.value() - before == len(wave) - plain
        count, seconds = _part("encode.pods")
        assert count == span0[0] + 1 and seconds > span0[1]


@pytest.mark.parametrize("deployment,carry", [("configuration", 5),
                                              ("batch", 0)])
def test_every_pod_of_the_configuration_counts_and_a_plain_pod_does_not(
        deployment, carry):
    """The share the cell reports as 100 %, and its control's 0."""
    if deployment == "configuration":
        config = _config(60)
        [template] = dep.pod_templates(config)
    else:
        config = _whole()
        template = dep.pod_templates(config)[0]
    warm = WarmPath(dep.nodes_of(config), dep.services(config))
    counted = incremental.constrained_pods()
    before = counted.value()
    warm.wave([(f"uid-b-{i}", template) for i in range(5)])
    assert counted.value() - before == carry


# -- the prewarm's fill trigger (solver/prewarm.py) ---------------------------
# Level-triggered, an axis measured in its own entries: a port, selector or
# service vocabulary below the fraction of its bucket queues nothing however
# many pod buckets the waves show; one at the fraction queues its next bucket
# once a shape; one host port is 1/32 of a port word. Driven as
# ``BatchScheduler._solve_snap`` drives it: ``IncrementalEncoder.fill_dims``
# against ``_dims_of`` of the host inputs.

POD_BUCKETS = [1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024]
VOCABULARY_AXES = ("Wp", "Wd", "Ks", "G", "B")


class _Compiled:
    def __init__(self):
        self.targets = []

    def __call__(self, target):
        self.targets.append(dict(target))


def _drained(c, timeout=10.0):
    deadline = time.monotonic() + timeout
    while c.pending() and time.monotonic() < deadline:
        time.sleep(0.005)
    assert c.pending() == 0
    return c


@pytest.fixture
def controller():
    rec = _Compiled()
    c = PrewarmController(rec, fill_fraction=0.75).start()
    yield c, rec
    c.stop()


def _advanced(targets, axis, shape):
    """The targets that advance ``axis`` past what ``shape`` holds."""
    return [t for t in targets if t[axis] > shape[axis]]


@pytest.mark.parametrize("deployment", ["configuration", "whole_provider"])
def test_a_vocabulary_below_the_fraction_queues_nothing_for_any_pod_bucket(
        controller, deployment):
    """Each deployment's own vocabulary, from its own encoder — one
    selector pair of eight columns; two host ports (1/16 of a word), two
    selector pairs, five group rows of eight — shown under every pod
    bucket, three times. Before PR 34 the two ports read as a full word
    and every pod bucket queued the two-word programs."""
    c, rec = controller
    config = _config(60) if deployment == "configuration" else _whole()
    templates = dep.pod_templates(config)
    nodes = [_api_node(n, t) for n, t in sorted(dep.nodes_of(config).items())]
    services = _api_services(dep.services(config))
    enc = incremental.IncrementalEncoder()
    for _ in range(3):
        for p in POD_BUCKETS:
            pending = [_api_pod(f"uid-{p}-{i}", templates[i % len(templates)])
                       for i in range(p)]
            snap = enc.encode(nodes, [], pending, services)
            shape = _dims_of(bs.snapshot_to_host_inputs(snap))
            c.observe(dict(enc.fill_dims(), P=p), shape)
    # the buckets' floors in both, which are the control's shapes too
    assert (shape["Wp"], shape["Ks"], shape["G"]) == (1, 8, 8)
    assert enc.fill_dims() == (
        {"Wp": 0.0, "Wd": 0.0, "Ks": 1, "G": 0, "B": 0}
        if deployment == "configuration" else
        {"Wp": 2 / 32, "Wd": 0.0, "Ks": 2, "G": 5, "B": 0})
    _drained(c)
    # the pod axis's own ladder, each bucket once, and nothing else
    assert sorted(t["P"] for t in rec.targets) == [2 * p for p in POD_BUCKETS]
    for axis in VOCABULARY_AXES:
        assert not _advanced(rec.targets, axis, shape), axis


def test_an_axis_at_the_fraction_queues_its_next_bucket_once_a_shape(
        controller):
    c, rec = controller
    shapes = [{"N": 64, "N1": 65, "P": p, "G": 8, "Wp": 1} for p in (4, 8, 16)]
    for shape in shapes:                       # 5 of 8: below
        c.observe({"P": 1, "G": 5}, shape)
    assert c.pending() == 0 and rec.targets == []
    c.observe({"P": 1, "G": 6}, shapes[0])     # 6 of 8: this shape's queues
    _drained(c)
    assert rec.targets == [dict(shapes[0], G=16)]
    for _ in range(3):                         # stands at 6: the other two,
        for shape in shapes:                   # once each, and no more
            c.observe({"P": 1, "G": 6}, shape)
    _drained(c)
    assert sorted(t["P"] for t in rec.targets) == [4, 8, 16]
    assert all(t["G"] == 16 and t["N1"] == 65 and t["Wp"] == 1
               for t in rec.targets)


@pytest.mark.parametrize("ports,queues", [(1, False), (23, False),
                                          (24, True), (32, True)])
def test_a_port_word_is_measured_in_ports_not_in_words(controller, ports,
                                                       queues):
    c, rec = controller
    config = _whole((3, 2, 1))
    nodes = [_api_node(n, t) for n, t in sorted(dep.nodes_of(config).items())]
    agent = dep.pod_templates(config)[6]
    pending = [_api_pod(f"uid-{i}", dict(agent, host_ports=[9100 + i]))
               for i in range(ports)]
    enc = incremental.IncrementalEncoder()
    snap = enc.encode(nodes, [], pending, [])
    shape = _dims_of(bs.snapshot_to_host_inputs(snap))
    assert shape["Wp"] == 1 and enc.fill_dims()["Wp"] == ports / 32
    c.observe(dict(enc.fill_dims(), P=ports), shape)
    _drained(c)
    assert [t["Wp"] for t in _advanced(rec.targets, "Wp", shape)] == \
        ([2] if queues else [])
