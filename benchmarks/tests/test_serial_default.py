"""``references/serial_default.py`` against the program's own two paths (the
wave encoder with ``batch_solver.solve``, host and score; the serial oracle,
host) on seeded random clusters with node selectors, host ports, two
services, two namespaces and four request sizes, wave after wave; against
``serial_resources.py`` where only resources are stated; and what each of
the two references refuses. The program is a second witness here: the
reference imports nothing of it."""

import random

import pytest

from benchmarks.references import serial_default as ref
from benchmarks.references import serial_resources as plain

NODE_TEMPLATES = [
    {"name": "small", "capacity": {"cpu": "4", "memory": "32Gi"},
     "labels": {}},
    {"name": "mid", "capacity": {"cpu": "8", "memory": "64Gi"},
     "labels": {"zone": "b"}},
    {"name": "big", "capacity": {"cpu": "16", "memory": "128Gi"},
     "labels": {"zone": "c", "disk": "ssd"}}]
POD_TEMPLATES = [
    {"name": "small", "namespace": "default", "labels": {},
     "limits": {"cpu": "100m", "memory": "500Mi"},
     "node_selector": {}, "host_ports": []},
    {"name": "zoned-web", "namespace": "default", "labels": {"app": "web"},
     "limits": {"cpu": "500m", "memory": "2Gi"},
     "node_selector": {"zone": "b"}, "host_ports": []},
    {"name": "ported-db", "namespace": "default",
     "labels": {"app": "db", "tier": "x"},
     "limits": {"cpu": "1", "memory": "4Gi"},
     "node_selector": {}, "host_ports": [8080]},
    {"name": "tenant-web", "namespace": "tenant", "labels": {"app": "web"},
     "limits": {"cpu": "250m", "memory": "1Gi"},
     "node_selector": {"disk": "ssd", "zone": "c"}, "host_ports": []}]
SERVICES = [
    {"name": "web", "namespace": "default", "selector": {"app": "web"}},
    {"name": "db", "namespace": "default", "selector": {"app": "db"}}]


def _deployment(seed):
    rng = random.Random(seed)
    nodes = {f"node-{i:05d}": NODE_TEMPLATES[rng.randrange(3)]
             for i in range(rng.randint(8, 64))}
    pods = [(f"uid-{seed}-{i:04d}", POD_TEMPLATES[rng.randrange(4)])
            for i in range(rng.randint(40, 120))]
    waves, at = [], 0
    while at < len(pods):
        size = rng.choice([1, 3, 8, 20])
        waves.append(pods[at:at + size])
        at += size
    return nodes, waves


def _api_objects(nodes):
    from kubernetes_tpu.api import types as api
    from kubernetes_tpu.api.quantity import Quantity

    def pod(uid, t):
        return api.Pod(
            metadata=api.ObjectMeta(name=uid, namespace=t["namespace"],
                                    uid=uid, labels=dict(t["labels"])),
            spec=api.PodSpec(
                node_selector=dict(t["node_selector"]),
                containers=[api.Container(
                    name="c", image="i",
                    ports=[api.ContainerPort(host_port=p, container_port=p)
                           for p in t["host_ports"]],
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


@pytest.mark.parametrize("seed", range(8))
def test_host_and_score_equal_the_program_s_pod_by_pod(seed):
    from kubernetes_tpu.models import batch_solver as bs
    from kubernetes_tpu.models.oracle import solve_serial
    from kubernetes_tpu.models.snapshot import encode_snapshot

    nodes, waves = _deployment(seed)
    api_nodes, services, api_pod = _api_objects(nodes)
    cluster = ref.Cluster(nodes, SERVICES)
    existing, seen = [], set()
    for wave in waves:
        pending = [api_pod(uid, t) for uid, t in wave]
        want = ref.solve_wave(cluster, wave)
        snap = encode_snapshot(api_nodes, existing, pending, services)
        chosen, scores = bs.solve(snap)
        hosts = bs.decisions_to_names(snap, chosen)
        got = [(h, int(s) if h is not None else -1)
               for h, s in zip(hosts, scores)]
        assert got == want, (seed, [t["name"] for _, t in wave])
        assert solve_serial(api_nodes, existing, pending, services) == \
            [h for h, _ in want]
        for (_uid, t), pod, host in zip(wave, pending, hosts):
            if host is not None:
                pod.spec.host = pod.status.host = host
                existing.append(pod)
                seen.add(t["name"])
    assert seen == {t["name"] for t in POD_TEMPLATES}


def test_it_places_where_the_rule_says_and_nowhere_else():
    nodes = {"node-0": NODE_TEMPLATES[0], "node-1": NODE_TEMPLATES[1],
             "node-2": NODE_TEMPLATES[1]}
    cluster = ref.Cluster(nodes, SERVICES)
    zoned, ported = POD_TEMPLATES[1], POD_TEMPLATES[2]
    first = ref.solve_wave(cluster, [("a", zoned), ("b", zoned)])
    # the selector keeps both off node-0; the second avoids its peer
    assert {h for h, _ in first} == {"node-1", "node-2"}
    assert [score for _, score in first] == [19, 19]
    ports = ref.solve_wave(cluster, [(u, ported) for u in "cdef"])
    assert sorted(h for h, _ in ports[:3]) == ["node-0", "node-1", "node-2"]
    assert ports[3] == (None, -1)                    # the port is taken
    tenant = ref.solve_wave(cluster, [("g", POD_TEMPLATES[3])])
    assert tenant == [(None, -1)]                    # no node is zone c


@pytest.mark.parametrize("seed", range(4))
def test_on_resources_alone_it_answers_as_serial_resources_does(seed):
    rng = random.Random(seed)
    nodes = {f"node-{i:05d}": dict(NODE_TEMPLATES[rng.randrange(3)],
                                   labels={})
             for i in range(rng.randint(4, 40))}
    sizes = [{"name": f"s{i}", "namespace": "default", "limits": limits}
             for i, limits in enumerate([
                 {"cpu": "100m", "memory": "500Mi"},
                 {"cpu": "500m", "memory": "2Gi"},
                 {"cpu": "2", "memory": "24Gi"}])]
    a, b = ref.Cluster(nodes), plain.Cluster(nodes)
    for w in range(12):
        wave = [(f"uid-{seed}-{w}-{i}", sizes[rng.randrange(3)])
                for i in range(rng.choice([1, 5, 30]))]
        solve = "solve_wave_uncommitted" if w % 4 == 3 else "solve_wave"
        got = getattr(ref, solve)(a, wave)
        assert got == getattr(plain, solve)(b, wave)


def test_its_control_comes_out_different():
    nodes = {f"node-{i:05d}": NODE_TEMPLATES[i % 3] for i in range(30)}
    wave = [(f"uid-{i}", POD_TEMPLATES[i % 3]) for i in range(60)]
    sound = ref.solve_wave(ref.Cluster(nodes, SERVICES), wave)
    control = ref.solve_wave_uncommitted(ref.Cluster(nodes, SERVICES), wave)
    assert sum(s[0] != c[0] for s, c in zip(sound, control)) >= 20


@pytest.mark.parametrize("what,build", [
    ("a node selector", lambda: plain.solve_wave(
        plain.Cluster({"n": NODE_TEMPLATES[0]}), [("u", POD_TEMPLATES[1])])),
    ("a host port", lambda: plain.solve_wave(
        plain.Cluster({"n": NODE_TEMPLATES[0]}), [("u", POD_TEMPLATES[2])])),
    ("a service", lambda: plain.Cluster({"n": NODE_TEMPLATES[0]}, SERVICES)),
    ("a node's third resource", lambda: plain.Cluster({"n": {
        "capacity": {"cpu": "4", "memory": "1Gi", "example.com/gpu": "1"}}})),
    ("a pod's third resource", lambda: plain.solve_wave(
        plain.Cluster({"n": NODE_TEMPLATES[0]}),
        [("u", {"name": "g", "namespace": "default", "limits": {
            "cpu": "1", "memory": "1Gi", "example.com/gpu": "1"}})])),
    ("serial_default: a node's third resource", lambda: ref.Cluster({"n": {
        "capacity": {"cpu": "4", "memory": "1Gi", "example.com/gpu": "1"}}})),
    ("serial_default: a pod two services select", lambda: ref.solve_wave(
        ref.Cluster({"n": NODE_TEMPLATES[0]}, SERVICES + [{
            "name": "tier", "namespace": "default",
            "selector": {"tier": "x"}}]), [("u", POD_TEMPLATES[2])]))])
def test_a_reference_raises_on_what_it_does_not_model(what, build):
    with pytest.raises(ValueError):
        build()
