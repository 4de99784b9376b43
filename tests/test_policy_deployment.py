"""``sched-policy-5000n`` (benchmarks/configs/) on the CPU: OpenShift Origin
3.x's default scheduler Policy — Region service affinity, Zone service
anti-affinity at weight 2 — over the scalability load test's services and
pods, the file's own templates cut by count (eight nodes a zone, three
namespaces), sent wave after wave down the path the wave loop takes:
``IncrementalEncoder`` under the Policy (``encode_delta`` with the binds of
the wave before), the resident planes, ``batch_solver.solve`` on the XLA scan
and on the Pallas kernel through the interpreter. Host and score have to
equal ``benchmarks/references/serial_policy.py``'s pod by pod; a program
that ignores the anchors, or drops the zone term, must not.

Then what the Policy asks of the wave loop: the encoder constructs, the
scheduler keeps it and its prewarm, and a wave reads its anchors in a span
and a counter of its own."""

import dataclasses
import json
import os

import numpy as np
import pytest

from benchmarks.harness import deployment as dep
from benchmarks.references import serial_policy as ref
from kubernetes_tpu.api import types as api
from kubernetes_tpu.api.quantity import Quantity
from kubernetes_tpu.models import batch_solver as bs
from kubernetes_tpu.models import incremental
from kubernetes_tpu.models import resident as rs
from kubernetes_tpu.models.policy import batch_policy_from
from kubernetes_tpu.scheduler import plugins, tpu_batch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAMESPACES = ("load-00", "load-01", "load-02")
HELD = {"big": 1, "medium": 3, "small": 40}     # groups kept, by class


def _file():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "sched-policy-5000n.json")) as f:
        return json.load(f)


def _cut(per_zone=8):
    """The file's own node pools, ``per_zone`` nodes each, and the first
    groups of each class that live in the first three namespaces."""
    config = _file()
    for pool in config["node_templates"]:
        pool["count"] = per_zone
    config["nodes"] = per_zone * len(config["node_templates"])
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


def _run(seed, count, pol_edit=None):
    """-> (pods whose host or score differ from serial_policy's, pods
    bound, pods the control places elsewhere, pods that read an anchor,
    the programs the waves took)."""
    config = _cut()
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


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("program", ["scan", "pallas"])
def test_the_served_program_decides_as_serial_policy_does(program, seed,
                                                          monkeypatch):
    """Every pod a member of a service: its first peer pins the region, the
    zone term at weight 2 spreads the rest over that region's zones."""
    monkeypatch.setenv("KTPU_PALLAS",
                       "interpret" if program == "pallas" else "off")
    count = 240 if program == "scan" else 160
    differ, placed, elsewhere, anchored, programs = _run(2 ** 31 + seed,
                                                         count)
    assert differ == 0
    assert programs == {program}
    assert placed == count
    assert anchored > count // 3
    assert elsewhere >= 20          # the control: the in-wave commit put off


def _ignore_anchors(monkeypatch):
    real = incremental.IncrementalEncoder._wave_anchors

    def blind(self, *a, **kw):
        pod_aff, anchor_vals0, has_anchor0 = real(self, *a, **kw)
        return pod_aff, np.full_like(anchor_vals0, -3), \
            np.zeros_like(has_anchor0)
    monkeypatch.setattr(incremental.IncrementalEncoder, "_wave_anchors",
                        blind)


@pytest.mark.parametrize("mutation", ["anchor_ignored", "zone_term_dropped"])
def test_a_program_without_either_term_is_caught(mutation, monkeypatch):
    monkeypatch.setenv("KTPU_PALLAS", "off")
    edit = None
    if mutation == "anchor_ignored":
        _ignore_anchors(monkeypatch)
    else:
        def edit(pol):
            return dataclasses.replace(pol, anti_affinity=())
    assert _run(2 ** 31 + 7, 240, edit)[0] >= 5


# -- what the Policy asks of the wave loop -------------------------------------

class _Config:
    """What ``BatchScheduler`` needs of a ``SchedulerConfig`` to encode a
    wave, under the configuration's Policy."""

    def __init__(self, nodes, policy):
        self.minion_lister = type("L", (), {"list": lambda _s: api.NodeList(
            items=nodes)})()
        self.modeler = type("M", (), {"list": lambda _s: []})()
        self.recorder = None
        self.provider, self.policy = "DefaultProvider", policy


def test_the_wave_loop_keeps_the_encoder_and_its_prewarm(monkeypatch):
    monkeypatch.setenv("KTPU_PREWARM", "auto")
    config = _cut()
    nodes, services = _api(config)
    policy = plugins.load_policy(json.dumps(config["scheduler"]["policy"]))
    incremental.IncrementalEncoder(batch_policy_from(None, policy))
    factory = type("F", (), {})()
    factory.service_store = type("S", (), {"list": lambda _s: services})()
    sched = tpu_batch.BatchScheduler(_Config(nodes, policy), factory, None)
    assert sched.batch_policy.affinity_labels == ("region",)
    assert sched._encoder is not None and sched._prewarm is not None
    pending = [_api_pod(f"p{i}", t) for i, t in
               enumerate(dep.pod_templates(config)[:8])]
    snap = sched._encode_wave(nodes, pending, services, lambda: [])
    assert snap.resident_epoch is not None
    assert snap.node_aff_vals.shape == (len(nodes), 1)
    assert snap.pod_aff_static.shape[1] == 1 and not snap.has_anchor0.any()


def test_the_span_and_the_counter_read_what_a_seeded_wave_holds():
    from kubernetes_tpu.util import metrics
    config = _cut()
    nodes, services = _api(config)
    templates = dep.pod_templates(config)[:6]
    enc = incremental.IncrementalEncoder(_policy(config))
    # one bound peer in each of the first three groups, one on a node that
    # is no longer listed
    bound = []
    for i, t in enumerate(templates[:3]):
        pod = _api_pod(f"b{i}", t)
        pod.spec.host = pod.status.host = nodes[i].metadata.name if i else \
            "gone"
        bound.append(pod)
    pending = [_api_pod(f"p{i}", t) for i, t in enumerate(templates * 2)]
    anchored, parts = incremental.anchored_pods(), metrics.wave_parts()
    a0, spans0 = anchored.value(), parts.count("encode.anchors")
    snap = enc.encode(nodes, bound, pending, services)
    assert anchored.value() - a0 == 6     # two pods of each peered group
    assert parts.count("encode.anchors") == spans0 + 1
    gid = snap.pod_gid[:len(pending)]
    # the group whose anchor is off the list: its pods are pinned nowhere
    ghost = gid[0]
    assert (snap.pod_aff_static[:len(pending)][gid == ghost, 0] == -100).all()
    assert (snap.pod_aff_static[:len(pending)][gid != ghost, 0] == -2).all()
    assert snap.has_anchor0[gid[:3]].all() and not snap.has_anchor0[
        gid[3:6]].any()
    # and without ServiceAffinity neither is touched
    plain = incremental.IncrementalEncoder()
    snap = plain.encode(nodes, bound, pending, services)
    assert snap.pod_aff_static is None and snap.node_aff_vals is None
    assert anchored.value() - a0 == 6
    assert parts.count("encode.anchors") == spans0 + 1
