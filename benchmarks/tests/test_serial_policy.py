"""``references/serial_policy.py``: with no Policy it answers as
``serial_default`` does, host and score, on seeded clusters built from each
accepted configuration's own templates; under a Policy it answers as the
program does, pod by pod and wave after wave — the wave encoder
(``encode_snapshot`` with ``batch_policy_from``) and both compiled solves,
the XLA scan and the Pallas kernel through the interpreter — on seeded
clusters over two regions of three zones with unlabelled nodes among them,
several services in two namespaces, selectors that pin a region. The
policies between them use every entry of the vocabulary, repeated entries
whose weights sum among them. The program is a second witness here: the
reference imports nothing of it."""

import json
import os
import random

import pytest

from benchmarks.harness import deployment as dep
from benchmarks.references import serial_default
from benchmarks.references import serial_policy as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIGS = ["sched-basic-500n", "sched-basic-5000n", "sched-basic-40960n",
           "sched-mixed-5000n", "sched-load-5000n"]


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


OPENSHIFT = _load("benchmarks", "tests", "fixtures",
                  "policy-load-5000n.json")["scheduler"]["policy"]
POLICIES = {
    "openshift": OPENSHIFT,
    # label presence and preference; EqualPriority with a weight
    "labels": {
        "predicates": [
            {"name": "PodFitsResources"}, {"name": "PodFitsPorts"},
            {"name": "HostName"}, {"name": "Schedulable"},
            {"name": "HasRegion", "argument": {"labelsPresence": {
                "labels": ["region"], "presence": True}}},
            {"name": "NotRetiring", "argument": {"labelsPresence": {
                "labels": ["retiring"], "presence": False}}}],
        "priorities": [
            {"name": "LeastRequestedPriority", "weight": 2},
            {"name": "Zoned", "weight": 1, "argument": {"labelPreference": {
                "label": "zone", "presence": True}}},
            {"name": "NoSsd", "weight": 3, "argument": {"labelPreference": {
                "label": "ssd", "presence": False}}},
            {"name": "EqualPriority", "weight": 1}]},
    # repeated entries: a predicate name given twice (the last stands),
    # priorities given twice (their weights sum), two anti-affinity labels
    "repeated": {
        "predicates": [
            {"name": "PodFitsResources"}, {"name": "MatchNodeSelector"},
            {"name": "NoDiskConflict"},
            {"name": "Rack", "argument": {"serviceAffinity": {
                "labels": ["zone"]}}},
            {"name": "Rack", "argument": {"serviceAffinity": {
                "labels": ["region"]}}}],
        "priorities": [
            {"name": "LeastRequestedPriority", "weight": 1},
            {"name": "ServiceSpreadingPriority", "weight": 1},
            {"name": "LeastRequestedPriority", "weight": 2},
            {"name": "ServiceSpreadingPriority", "weight": 2},
            {"name": "Zone", "weight": 1, "argument": {
                "serviceAntiAffinity": {"label": "zone"}}},
            {"name": "Region", "weight": 1, "argument": {
                "serviceAntiAffinity": {"label": "region"}}},
            {"name": "EqualPriority", "weight": 0}]},
    # two service affinities at once, and a presence check beside them
    "two_affinities": {
        "predicates": [
            {"name": "PodFitsResources"}, {"name": "PodFitsPorts"},
            {"name": "Region", "argument": {"serviceAffinity": {
                "labels": ["region"]}}},
            {"name": "Zone", "argument": {"serviceAffinity": {
                "labels": ["zone"]}}},
            {"name": "HasZone", "argument": {"labelsPresence": {
                "labels": ["zone"], "presence": True}}}],
        "priorities": [
            {"name": "LeastRequestedPriority", "weight": 1},
            {"name": "Spread", "weight": 3, "argument": {
                "serviceAntiAffinity": {"label": "region"}}}]},
    # no PodFitsResources: a node may fill past its capacity; no priority:
    # every node scores 1
    "bare": {
        "predicates": [{"name": "MatchNodeSelector"},
                       {"name": "PodFitsPorts"}],
        "priorities": []},
    # every weight 0: no pod is placed
    "silent": {
        "predicates": [{"name": "PodFitsResources"}],
        "priorities": [
            {"name": "LeastRequestedPriority", "weight": 0},
            {"name": "Zone", "weight": 0, "argument": {
                "serviceAntiAffinity": {"label": "zone"}}}]},
}


def test_the_policies_use_every_entry_of_the_vocabulary():
    names, arguments = set(), set()
    for policy in POLICIES.values():
        for entry in policy["predicates"] + policy["priorities"]:
            (arguments.update(entry["argument"]) if "argument" in entry
             else names.add(entry["name"]))
        dep.scheduler({"scheduler": {"policy": policy}})
    assert names == set(dep.PREDICATES) | set(dep.PRIORITIES)
    assert arguments == set(dep.PREDICATE_ARGUMENTS) | \
        set(dep.PRIORITY_ARGUMENTS)


# -- with no Policy: serial_default's answers --------------------------------

def _from_config(name: str, seed: int):
    """A small cluster of the configuration's own node templates, pods of
    a dozen of its pod templates, its services, waves of seeded sizes."""
    config = _load("benchmarks", "configs", name + ".json")
    rng = random.Random(f"{name}/{seed}")
    node_templates = dep.node_templates(config)
    nodes = {f"node-{i:05d}": rng.choice(node_templates)
             for i in range(rng.randint(6, 40))}
    templates = dep.pod_templates(config)
    some = [rng.choice(templates) for _ in range(12)]
    pods = [(f"uid-{seed}-{i:04d}", rng.choice(some))
            for i in range(rng.randint(60, 240))]
    waves, at = [], 0
    while at < len(pods):
        size = rng.choice([1, 4, 16, 50])
        waves.append(pods[at:at + size])
        at += size
    return nodes, dep.services(config), waves


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("name", CONFIGS)
def test_with_no_policy_it_answers_as_serial_default(name, seed):
    nodes, services, waves = _from_config(name, seed)
    mine = ref.Cluster(nodes, services)
    theirs = serial_default.Cluster(nodes, services)
    for w, wave in enumerate(waves):
        solve = "solve_wave_uncommitted" if w % 5 == 4 else "solve_wave"
        assert getattr(ref, solve)(mine, wave) == \
            getattr(serial_default, solve)(theirs, wave), (name, seed, w)


# -- under a Policy: the program's answers -----------------------------------

NODE_TEMPLATES = [
    {"name": f"{r}-{z}", "capacity": {"cpu": cpu, "memory": memory},
     "labels": dict({"region": r, "zone": f"{r}-{z}"}, **extra)}
    for r, z, cpu, memory, extra in (
        ("r1", "a", "4", "32Gi", {}), ("r1", "b", "8", "64Gi", {}),
        ("r1", "c", "4", "32Gi", {"ssd": "true"}),
        ("r2", "a", "8", "64Gi", {}), ("r2", "b", "4", "32Gi", {}),
        ("r2", "c", "16", "128Gi", {"ssd": "true", "retiring": "yes"}))] + [
    {"name": "bare", "capacity": {"cpu": "4", "memory": "32Gi"},
     "labels": {}},
    {"name": "region-only", "capacity": {"cpu": "8", "memory": "64Gi"},
     "labels": {"region": "r2"}}]
POD_TEMPLATES = [
    {"name": "web", "namespace": "default", "labels": {"app": "web"},
     "limits": {"cpu": "500m", "memory": "2Gi"}},
    {"name": "web-r2", "namespace": "default",
     "labels": {"app": "web", "pin": "r2"},
     "limits": {"cpu": "250m", "memory": "1Gi"},
     "node_selector": {"region": "r2"}},
    {"name": "db", "namespace": "default", "labels": {"app": "db"},
     "limits": {"cpu": "1", "memory": "4Gi"}, "host_ports": [5432]},
    {"name": "cache", "namespace": "tenant", "labels": {"app": "cache"},
     "limits": {"cpu": "2", "memory": "8Gi"}},
    {"name": "api-r1", "namespace": "tenant", "labels": {"app": "api"},
     "limits": {"cpu": "100m", "memory": "500Mi"},
     "node_selector": {"region": "r1"}},
    {"name": "lone", "namespace": "default", "labels": {},
     "limits": {"cpu": "100m", "memory": "500Mi"}}]
SERVICES = [
    {"name": "web", "namespace": "default", "selector": {"app": "web"}},
    {"name": "db", "namespace": "default", "selector": {"app": "db"}},
    {"name": "cache", "namespace": "tenant", "selector": {"app": "cache"}},
    {"name": "api", "namespace": "tenant", "selector": {"app": "api"}}]


def _cluster(seed):
    rng = random.Random(seed)
    nodes = {f"node-{i:05d}": NODE_TEMPLATES[rng.randrange(8)]
             for i in range(rng.randint(12, 40))}
    pods = [(f"uid-{seed}-{i:04d}", POD_TEMPLATES[rng.randrange(6)])
            for i in range(rng.randint(60, 140))]
    waves, at = [], 0
    while at < len(pods):
        size = rng.choice([1, 3, 8, 20])
        waves.append(pods[at:at + size])
        at += size
    return nodes, waves


def _api(nodes):
    from kubernetes_tpu.api import types as api
    from kubernetes_tpu.api.quantity import Quantity

    def pod(uid, t):
        return api.Pod(
            metadata=api.ObjectMeta(name=uid, namespace=t["namespace"],
                                    uid=uid, labels=dict(t["labels"])),
            spec=api.PodSpec(
                node_selector=dict(t.get("node_selector", {})),
                containers=[api.Container(
                    name="c", image="i",
                    ports=[api.ContainerPort(host_port=p, container_port=p)
                           for p in t.get("host_ports", [])],
                    resources=api.ResourceRequirements(limits={
                        k: Quantity(v) for k, v in t["limits"].items()}))]))

    api_nodes = [api.Node(
        metadata=api.ObjectMeta(name=n, labels=dict(t["labels"])),
        spec=api.NodeSpec(capacity={k: Quantity(v)
                                    for k, v in t["capacity"].items()}))
        for n, t in sorted(nodes.items())]
    services = [api.Service(
        metadata=api.ObjectMeta(name=s["name"], namespace=s["namespace"]),
        spec=api.ServiceSpec(port=80, selector=dict(s["selector"])))
        for s in SERVICES]
    return api_nodes, services, pod


def _program(snap, pol):
    """Host and score of each pod by the XLA scan and, where the wave is
    in its domain, the Pallas kernel through the interpreter; both have to
    agree."""
    import numpy as np
    from kubernetes_tpu.models import batch_solver as bs
    from kubernetes_tpu.ops import pallas_solver

    inp = bs.snapshot_to_inputs(snap)
    answers = [bs.solve_jit(inp, pol=pol)]
    kernel = pallas_solver.eligible(inp, pol, False, bs.peer_bound_of(snap))
    if kernel:
        answers.append(pallas_solver.solve_pallas(inp, pol=pol,
                                                  interpret=True))
    out = []
    for chosen, scores in answers:
        chosen, scores = np.asarray(chosen), np.asarray(scores)
        hosts = bs.decisions_to_names(snap, chosen)
        out.append([(h, int(s) if h is not None else -1)
                    for h, s in zip(hosts, scores)])
    assert all(o == out[0] for o in out)
    return out[0], kernel


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("policy", sorted(POLICIES))
def test_under_a_policy_it_answers_as_the_program_pod_by_pod(policy, seed):
    from kubernetes_tpu.models.policy import batch_policy_from
    from kubernetes_tpu.models.snapshot import encode_snapshot
    from kubernetes_tpu.scheduler import plugins

    stated = POLICIES[policy]
    pol = batch_policy_from("DefaultProvider",
                            plugins.load_policy(json.dumps(stated)))
    nodes, waves = _cluster(seed)
    api_nodes, services, api_pod = _api(nodes)
    cluster = ref.Cluster(nodes, SERVICES, policy=stated)
    existing, placed, kernel_waves = [], 0, 0
    for wave in waves:
        pending = [api_pod(uid, t) for uid, t in wave]
        want = ref.solve_wave(cluster, wave)
        snap = encode_snapshot(api_nodes, existing, pending, services,
                               policy=pol)
        got, kernel = _program(snap, pol)
        kernel_waves += kernel
        assert got == want, (policy, seed, [t["name"] for _, t in wave])
        for pod, (host, _score) in zip(pending, got):
            if host is not None:
                pod.spec.host = pod.status.host = host
                existing.append(pod)
                placed += 1
    if policy == "silent":
        assert placed == 0 and kernel_waves == 0
    else:
        assert placed > len(waves) and kernel_waves == len(waves)


def test_the_first_peer_pins_the_region_and_zones_spread_within_it():
    nodes = {f"node-{i:05d}": NODE_TEMPLATES[i % 6] for i in range(12)}
    cluster = ref.Cluster(nodes, SERVICES, policy=OPENSHIFT)
    web = POD_TEMPLATES[0]
    first = ref.solve_wave(cluster, [("w0", web)])[0][0]
    region = nodes[first]["labels"]["region"]
    rest = ref.solve_wave(cluster, [(f"w{i}", web) for i in range(1, 6)])
    assert {nodes[h]["labels"]["region"] for h, _ in rest} == {region}
    # six peers over the region's three zones: two a zone
    zones = [nodes[h]["labels"]["zone"] for h in [first] + [h for h, _ in
                                                             rest]]
    assert sorted(zones.count(z) for z in set(zones)) == [2, 2, 2]
    # a selector that names the other region wins over the first peer
    pinned = dict(web, name="web-other",
                  node_selector={"region": "r2" if region == "r1" else "r1"})
    host, _ = ref.solve_wave(cluster, [("w9", pinned)])[0]
    assert nodes[host]["labels"]["region"] != region


def test_an_unlabelled_node_scores_nothing_for_anti_affinity():
    nodes = {"node-0": NODE_TEMPLATES[6], "node-1": NODE_TEMPLATES[0]}
    policy = {"predicates": [{"name": "PodFitsResources"}],
              "priorities": [{"name": "Zone", "weight": 1, "argument": {
                  "serviceAntiAffinity": {"label": "zone"}}}]}
    cluster = ref.Cluster(nodes, SERVICES, policy=policy)
    assert ref.solve_wave(cluster, [("a", POD_TEMPLATES[0])]) == \
        [("node-1", 10)]


def test_its_control_comes_out_different():
    nodes = {f"node-{i:05d}": NODE_TEMPLATES[i % 8] for i in range(24)}
    wave = [(f"uid-{i}", POD_TEMPLATES[i % 6]) for i in range(80)]
    sound = ref.solve_wave(ref.Cluster(nodes, SERVICES, OPENSHIFT), wave)
    control = ref.solve_wave_uncommitted(
        ref.Cluster(nodes, SERVICES, OPENSHIFT), wave)
    assert sum(s[0] != c[0] for s, c in zip(sound, control)) >= 20


@pytest.mark.parametrize("policy", [
    {"predicates": [{"name": "CheckVolumeCount"}]},
    {"priorities": [{"name": "MostRequestedPriority", "weight": 1}]},
    {"priorities": [{"name": "LeastRequestedPriority", "weight": -1}]}])
def test_it_raises_on_what_it_does_not_model(policy):
    with pytest.raises(ValueError):
        ref.Cluster({"n": NODE_TEMPLATES[0]}, (), policy=policy)
