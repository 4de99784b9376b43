"""``sched-topology-5000n`` (benchmarks/configs/) on the CPU: the
three-level topology Policy of OpenShift Origin 3.x's admin guide — region
and zone service affinity, rack service anti-affinity at weight 1 — over
the scalability load test's services and pods. The file's own 126 rack
templates are cut by count (``PER_RACK`` nodes a rack, three namespaces), so
the rack label keeps its 126 values, past the 64 the kernel once held one
plane each for. The waves go down the path the wave loop takes:
``IncrementalEncoder`` under the Policy (``encode_delta`` with the binds of
the wave before), the resident planes, ``batch_solver.solve`` on the XLA scan
and on the Pallas kernel through the interpreter. Host and score have to
equal ``benchmarks/references/serial_policy.py``'s pod by pod; a program
that drops the spread term, folds only the first 64 rack values, ignores
the anchors or anchors the region alone must not. The same nodes under
three spread terms (region, zone and rack at weight 1, no affinity) hold
the kernel's fold of several labels of their own value counts (2, 6, 126).

Then the kernel's one VMEM account: the group cap the wave loop cuts at
(``max_groups``) is a wave ``eligible`` takes, under any policy; and what
the wave loop reads of it, the zone span and the cap counter."""

import dataclasses
import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import deployment as dep
from benchmarks.references import serial_policy as ref
from kubernetes_tpu.api import types as api
from kubernetes_tpu.api.quantity import Quantity
from kubernetes_tpu.models import batch_solver as bs
from kubernetes_tpu.models import incremental
from kubernetes_tpu.models import resident as rs
from kubernetes_tpu.models.policy import BatchPolicy, batch_policy_from
from kubernetes_tpu.ops import pallas_solver
from kubernetes_tpu.scheduler import plugins, tpu_batch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAMESPACES = ("load-00", "load-01", "load-02")
HELD = {"big": 1, "medium": 3, "small": 40}     # groups kept, by class
PER_RACK = 2
LABELS = ("region", "zone", "rack")
# the kernel's fold of several labels: the published Policy's default
# entries and one spread term a level, no affinity
SPREADS = {
    "predicates": [{"name": n} for n in (
        "MatchNodeSelector", "PodFitsResources", "PodFitsPorts",
        "NoDiskConflict")],
    "priorities": [
        {"name": "LeastRequestedPriority", "weight": 1},
        {"name": "ServiceSpreadingPriority", "weight": 1}] + [
        {"name": f"{label.capitalize()}Spread", "weight": 1,
         "argument": {"serviceAntiAffinity": {"label": label}}}
        for label in LABELS]}


def _file():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "sched-topology-5000n.json")) as f:
        return json.load(f)


def _cut(per_rack=PER_RACK, policy=None):
    """The file's own 126 racks, ``per_rack`` nodes each, and the first
    groups of each class that live in the first three namespaces; under
    ``policy`` in place of the file's where given."""
    config = _file()
    if policy is not None:
        config["scheduler"]["policy"] = dict(
            config["scheduler"]["policy"], **policy)
    for pool in config["node_templates"]:
        pool["count"] = per_rack
    config["nodes"] = per_rack * len(config["node_templates"])
    left, names = dict(HELD), set()
    for t in config["pod_templates"]:
        cls = t["name"].split("-")[1]
        if t["namespace"] in NAMESPACES and left.get(cls, 0) > 0:
            left[cls] -= 1
            names.add(t["name"])
    config["pod_templates"] = [t for t in config["pod_templates"]
                               if t["name"] in names]
    config["services"] = [s for s in config["services"]
                          if s["name"] in names]
    return config


def _policy(config):
    return batch_policy_from(config["scheduler"]["provider"],
                             plugins.load_policy(json.dumps(
                                 config["scheduler"]["policy"])))


def _api_pod(uid, t):
    return api.Pod(
        metadata=api.ObjectMeta(name=uid, namespace=t["namespace"], uid=uid,
                                labels=dict(t["labels"])),
        spec=api.PodSpec(
            node_selector=dict(t["node_selector"]),
            containers=[api.Container(
                name=t["container"], image=t["image"],
                resources=api.ResourceRequirements(limits={
                    k: Quantity(v) for k, v in t["limits"].items()}))]))


def _api(config):
    nodes = [api.Node(
        metadata=api.ObjectMeta(name=n, labels=dict(t["labels"])),
        spec=api.NodeSpec(capacity={k: Quantity(v)
                                    for k, v in t["capacity"].items()}))
        for n, t in sorted(dep.nodes_of(config).items())]
    services = [api.Service(
        metadata=api.ObjectMeta(name=s["name"], namespace=s["namespace"]),
        spec=api.ServiceSpec(port=80, selector=dict(s["selector"])))
        for s in dep.services(config)]
    return nodes, services


def _waves(templates, seed, count, sizes=(1, 4, 16, 60, 60)):
    import random
    rng = random.Random(seed)
    plan = dep.pod_plan(templates, "window", seed, count)[:count]
    pods = [(f"uid-{seed}-{i:04d}", templates[t]) for i, t in enumerate(plan)]
    waves, at = [], 0
    while at < len(pods):
        size = rng.choice(sizes)
        waves.append(pods[at:at + size])
        at += size
    return waves


def _run(seed, count, pol_edit=None, policy=None):
    """-> (pods whose host or score differ from serial_policy's, pods
    bound, pods the control places elsewhere, pods that read an anchor,
    the programs the waves took)."""
    config = _cut(policy=policy)
    templates = dep.pod_templates(config)
    nodes, services = _api(config)
    pol = _policy(config)
    enc = incremental.IncrementalEncoder(pol_edit(pol) if pol_edit else pol)
    planes = rs.ResidentPlanes()
    stated = config["scheduler"]["policy"]
    cluster = ref.Cluster(dep.nodes_of(config), dep.services(config), stated)
    control = ref.Cluster(dep.nodes_of(config), dep.services(config), stated)
    anchored = incremental.anchored_pods()
    programs0, anchored0 = dict(bs.wave_programs().by_label()), \
        anchored.value()
    bound, upserted = [], []
    differ = elsewhere = placed = 0
    for wave in _waves(templates, seed, count):
        pending = [_api_pod(uid, t) for uid, t in wave]
        snap = enc.encode_delta(nodes, upserted, [], pending, services)
        if snap is None:                  # the first wave: nothing held yet
            snap = enc.encode(nodes, bound, pending, services)
        upserted = []
        chosen, scores = bs.solve(snap, host=planes.host_inputs(snap),
                                  resident=planes)
        hosts = bs.decisions_to_names(snap, chosen)
        got = [(h, int(s) if h is not None else -1)
               for h, s in zip(hosts, scores)]
        want = ref.solve_wave(cluster, wave)
        differ += sum(g != w for g, w in zip(got, want))
        elsewhere += sum(c[0] != w[0] for c, w in zip(
            ref.solve_wave_uncommitted(control, wave), want))
        for pod, h in zip(pending, hosts):
            if h is not None:
                pod.spec.host = pod.status.host = h
                bound.append(pod)
                upserted.append(pod)
                placed += 1
    programs = {k[0] for k, n in bs.wave_programs().by_label().items()
                if n - programs0.get(k, 0)}
    return differ, placed, elsewhere, anchored.value() - anchored0, programs


def test_the_cut_keeps_every_level_and_126_rack_values():
    config = _cut()
    values = {label: {t["labels"][label] for t in config["node_templates"]}
              for label in LABELS}
    assert {k: len(v) for k, v in values.items()} == \
        {"region": 2, "zone": 6, "rack": 126}
    pol = _policy(config)
    assert pol.affinity_labels == ("region", "zone")
    assert pol.anti_affinity == (("rack", 1),)
    nodes, services = _api(config)
    templates = dep.pod_templates(config)
    snap = incremental.IncrementalEncoder(pol).encode(
        nodes, [], [_api_pod("p0", templates[0])], services)
    assert snap.zone_counts0.shape[0] == 1 and snap.zone_counts0.shape[2] \
        == 126
    # under three spread terms the encoder's V is the largest vocabulary;
    # each label keeps its own
    config = _cut(policy=SPREADS)
    snap = incremental.IncrementalEncoder(_policy(config)).encode(
        nodes, [], [_api_pod("p0", templates[0])], services)
    assert snap.zone_counts0.shape[0] == 3 and snap.zone_counts0.shape[2] \
        == 126
    assert [int(z.max()) + 1 for z in snap.node_zone] == [2, 6, 126]


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("program", ["scan", "pallas"])
@pytest.mark.parametrize("policy", ["published", "three_spreads"])
def test_the_served_program_decides_as_serial_policy_does(policy, program,
                                                          seed, monkeypatch):
    """Every pod a member of a service. Under the published Policy its
    first peer pins the region and zone, and the rack term spreads the rest
    over that zone's 21 racks — the racks of the second region are codes 63
    to 125. Under three spread terms the pods spread over the whole
    cluster, each label folded over its own 2, 6 or 126 values."""
    monkeypatch.setenv("KTPU_PALLAS",
                       "interpret" if program == "pallas" else "off")
    count = 240 if program == "scan" else 160
    differ, placed, elsewhere, anchored, programs = _run(
        2 ** 31 + seed, count,
        policy=SPREADS if policy == "three_spreads" else None)
    assert differ == 0
    assert programs == {program}
    assert placed == count
    if policy == "published":
        assert anchored > count // 3
    else:
        assert anchored == 0
    assert elsewhere >= 20          # the control: the in-wave commit put off


def _ignore_anchors(monkeypatch):
    real = incremental.IncrementalEncoder._wave_anchors

    def blind(self, *a, **kw):
        pod_aff, anchor_vals0, has_anchor0 = real(self, *a, **kw)
        return pod_aff, np.full_like(anchor_vals0, -3), \
            np.zeros_like(has_anchor0)
    monkeypatch.setattr(incremental.IncrementalEncoder, "_wave_anchors",
                        blind)


@pytest.fixture
def fresh_kernel():
    """A mutation of what the kernel traces must not reach a program
    compiled before it, nor leave one behind."""
    pallas_solver._solve_pallas_x32.clear_cache()
    yield
    pallas_solver._solve_pallas_x32.clear_cache()


@pytest.mark.parametrize("mutation", [
    "rack_dropped", "racks_past_64_unfolded", "anchors_ignored",
    "zone_anchor_dropped", "spreads:region_dropped", "spreads:zone_dropped",
    "spreads:racks_past_64_unfolded"])
def test_a_program_without_any_term_is_caught(mutation, monkeypatch,
                                              fresh_kernel):
    count, edit, policy = 240, None, None
    if mutation.startswith("spreads:"):
        mutation, policy = mutation.split(":")[1], SPREADS
    monkeypatch.setenv("KTPU_PALLAS", "off")
    if mutation.endswith("_dropped") and mutation != "zone_anchor_dropped":
        level = mutation.split("_")[0]

        def edit(pol):
            return dataclasses.replace(pol, anti_affinity=tuple(
                (label, w) for label, w in pol.anti_affinity
                if label != level))
    elif mutation == "zone_anchor_dropped":
        # a program that anchors the first label of the predicate only

        def edit(pol):
            return dataclasses.replace(
                pol, affinity_labels=pol.affinity_labels[:1])
    elif mutation == "racks_past_64_unfolded":
        # the kernel folds each label up to the 64th value, the old
        # _MAX_V domain, and no further
        monkeypatch.setenv("KTPU_PALLAS", "interpret")
        count = 160
        real = pallas_solver._value_counts
        monkeypatch.setattr(pallas_solver, "_value_counts",
                            lambda zidx: jnp.minimum(real(zidx), 64))
    else:
        _ignore_anchors(monkeypatch)
    assert _run(2 ** 31 + 7, count, edit, policy)[0] >= 5


# -- one VMEM account for the kernel and the wave loop's cut ------------------

def _wave_shapes(N, G, *, L=0, A=0, V=1, R=2, P=8):
    """The shapes ``eligible`` reads of a wave, and nothing else."""
    def s(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)
    return SimpleNamespace(
        cap=s(N, R), band_prio=None, group_counts=s(G, N + 1),
        node_ports=s(N, 1, dtype=jnp.uint32),
        node_pds=s(N, 1, dtype=jnp.uint32), req=s(P, R),
        zone_idx=s(A, N), zone_counts0=s(A, G, V), node_aff_vals=s(N, L))


@pytest.mark.parametrize("N", [1, 128, 1_000, 5_000, 8_192, 12_000,
                               20_000, 32_640])
def test_the_group_cap_is_a_wave_the_kernel_takes(N):
    """For every policy the kernel models, a wave at ``max_groups``' cap
    passes ``eligible`` and the next power of two does not, wherever the
    cap is below ``_MAX_G``: the two read one account."""
    caps = set()
    for L in range(3):
        for A in range(4):
            for prefs in (False, True):
                pol = BatchPolicy(
                    affinity_labels=tuple(f"l{i}" for i in range(L)),
                    anti_affinity=tuple((f"z{a}", 1) for a in range(A)),
                    label_prefs=(("x", True, 1),) if prefs else ())
                cap = pallas_solver.max_groups(N, pol=pol)
                caps.add(cap)
                for V in (1, 6, 64, 65, 128, 256):
                    assert pallas_solver.eligible(
                        _wave_shapes(N, cap, L=L, A=A, V=V), pol, False, 0)
                    if cap < pallas_solver._MAX_G:
                        assert not pallas_solver.eligible(
                            _wave_shapes(N, 2 * cap, L=L, A=A, V=V), pol,
                            False, 0)
    assert caps <= {8, 16, 32, 64, 128, 256}
    if N == 5_000:
        assert caps == {256}        # the topology cell keeps the full cap
    if N == 20_000:
        assert caps == {64}


def test_the_encoder_caps_its_groups_at_its_policy():
    enc = incremental.IncrementalEncoder(_policy(_cut()))
    # at 5,505 nodes the region and zone planes, the rack's zone-index
    # plane and the anchor rows are what tips 256 rows over the budget
    assert enc.group_cap(5_505) == pallas_solver.max_groups(
        5_505, pol=enc.policy) == 128
    assert pallas_solver.max_groups(5_505) == 256
    assert enc.group_cap(5_504) == 256


def test_the_zone_span_and_the_cap_counter_read_a_seeded_wave(monkeypatch):
    from kubernetes_tpu.util import metrics
    monkeypatch.setenv("KTPU_PREWARM", "off")
    config = _cut()
    nodes, services = _api(config)
    templates = dep.pod_templates(config)[:6]
    parts = metrics.wave_parts()
    spans0 = parts.count("encode.zones")
    # one bound peer in the first group: its zone counts fill that row
    bound = [_api_pod("b0", templates[0])]
    bound[0].spec.host = bound[0].status.host = nodes[0].metadata.name
    pending = [_api_pod(f"p{i}", t) for i, t in enumerate(templates)]
    enc = incremental.IncrementalEncoder(_policy(config))
    snap = enc.encode(nodes, bound, pending, services)
    assert parts.count("encode.zones") == spans0 + 1
    row = snap.pod_gid[0]
    assert int(snap.zone_counts0[0, row].sum()) == 1
    assert int(snap.zone_counts0.sum()) == 1
    # without anti-affinity the span is not opened
    incremental.IncrementalEncoder().encode(nodes, bound, pending, services)
    assert parts.count("encode.zones") == spans0 + 1
    # the wave loop adds the cap it cut at, once a wave
    policy = plugins.load_policy(json.dumps(config["scheduler"]["policy"]))
    factory = type("F", (), {})()
    factory.service_store = type("S", (), {"list": lambda _s: services})()
    sched = tpu_batch.BatchScheduler(_Config(nodes, policy), factory, None)
    rows = tpu_batch._wave_metrics().group_cap_rows
    before = rows.value()
    assert sched._cut_at_group_cap(pending, services, len(nodes)) == pending
    assert rows.value() - before == sched._encoder.group_cap(len(nodes)) \
        == 256


class _Config:
    """What ``BatchScheduler`` needs of a ``SchedulerConfig`` to cut a
    wave, under the configuration's Policy."""

    def __init__(self, nodes, policy):
        self.minion_lister = type("L", (), {"list": lambda _s: api.NodeList(
            items=nodes)})()
        self.modeler = type("M", (), {"list": lambda _s: []})()
        self.recorder = None
        self.provider, self.policy = "DefaultProvider", policy
