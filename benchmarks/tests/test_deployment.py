"""The seam between a configuration's description of its deployment and the
two sides that take it: what the feeder posts and the control plane
registers for the accepted configurations is byte for byte what the parent
tree (a30de1f) posted and registered (``goldens/``, captured there), and
the pod plan offers every seed the same mix."""

import collections
import hashlib
import json
import os

import pytest

from benchmarks import feeder
from benchmarks.harness import control_plane as cpl
from benchmarks.harness import deployment as dep

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
ACCEPTED = ["sched-basic-500n", "sched-basic-5000n", "sched-basic-40960n"]


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _fixture(nodes=None):
    doc = _load(HERE, "fixtures", "three-class-5000n.json")
    doc["namespace"] = "default"
    if nodes:
        doc["nodes"] = sum(nodes)
        for t, count in zip(doc["node_templates"], nodes):
            t["count"] = count
    return doc


def _encoder():
    from kubernetes_tpu.client.http import HTTPTransport
    t = HTTPTransport("http://127.0.0.1:1")
    return lambda obj: t.scheme.encode(obj, t.version)


@pytest.mark.parametrize("name", ACCEPTED)
def test_an_accepted_configuration_posts_what_the_parent_posted(name):
    config = _load(ROOT, "benchmarks", "configs", name + ".json")
    golden = _load(HERE, "goldens", name + ".json")
    encode = _encoder()
    templates = dep.pod_templates(config)
    plans = {"window": dep.pod_plan(templates, "window", golden["seed"], 1)}
    factory = feeder.PodFactory(templates, plans, golden["seed"])
    made = [factory.make() for _ in golden["pods"]]
    assert [encode(pod) for _, pod in made] == golden["pods"]
    assert {index for index, _ in made} == {0}
    nodes = [encode(n) for n in cpl.make_nodes(config, golden["seed"])]
    assert len(nodes) == golden["nodes"]
    assert nodes[:3] == golden["nodes_first"]
    assert hashlib.sha256("\n".join(nodes).encode()).hexdigest() == \
        golden["nodes_sha256"]
    assert cpl.make_services(config) == []


def test_a_node_s_template_follows_from_its_name_and_not_the_seed():
    config = _fixture()
    nodes = dep.nodes_of(config)
    assert list(nodes) == sorted(nodes) and len(nodes) == 5000
    assert nodes["node-02999"]["name"] == "small"
    assert nodes["node-03000"]["name"] == nodes["node-04499"]["name"] \
        == "mid-zone-b"
    assert nodes["node-04500"]["name"] == "big-zone-c"
    a, b = cpl.make_nodes(config, 1), cpl.make_nodes(config, 2)
    assert [n.metadata.name for n in a] != [n.metadata.name for n in b]
    by_name = {n.metadata.name: n for n in b}
    for n in a:
        assert n == by_name[n.metadata.name]
    node = by_name["node-04999"]
    assert node.metadata.labels == {"zone": "c"}
    assert node.spec.capacity["cpu"].milli_value() == 16000
    assert by_name["node-00000"].metadata.labels == {}


def test_pods_services_and_nodes_carry_what_their_templates_state():
    config = _fixture()
    templates = dep.pod_templates(config)
    plans = {"window": dep.pod_plan(templates, "window", 5, 1000)}
    factory = feeder.PodFactory(templates, plans, 5)
    seen = {}
    for _ in range(1000):
        index, pod = factory.make("window")
        seen.setdefault(templates[index]["name"], pod)
    ported, zoned, small = seen["ported"], seen["zoned"], seen["small"]
    assert ported.metadata.labels == {"app": "ported"}
    assert [p.host_port for p in ported.spec.containers[0].ports] == [8080]
    assert zoned.spec.node_selector == {"zone": "b"}
    assert zoned.spec.containers[0].resources.limits["cpu"].milli_value() \
        == 500
    assert small.spec.node_selector == {} and small.metadata.labels == {}
    assert not small.spec.containers[0].ports
    [svc] = cpl.make_services(config)
    assert (svc.metadata.name, svc.metadata.namespace) == ("ported",
                                                           "default")
    assert svc.spec.selector == {"app": "ported"}


def test_the_plan_offers_every_seed_the_same_mix_in_another_order():
    templates = dep.pod_templates(_fixture())
    a = dep.pod_plan(templates, "window", 1, 2500)
    b = dep.pod_plan(templates, "window", 2, 2500)
    assert len(a) == len(b) == 3000 and a != b       # whole blocks
    for plan in (a, b):
        for at in range(0, 3000, dep.PLAN_BLOCK):
            assert collections.Counter(plan[at:at + dep.PLAN_BLOCK]) == \
                {0: 600, 1: 300, 2: 100}
    assert dep.pod_plan(templates, "window", 1, 2500) == a
    assert dep.pod_plan(templates, "warm", 1, 2500) != a   # its own draw
    # weights that do not divide the block: the block is their sum's multiple
    odd = [dict(t, weight=w) for t, w in zip(templates, (7, 3, 1))]
    plan = dep.pod_plan(odd, "window", 3, 1)
    assert collections.Counter(plan) == {0: 630, 1: 270, 2: 90}
    # one template: the plan is constant
    assert set(dep.pod_plan(templates[:1], "window", 9, 10)) == {0}


def test_init_pods_come_in_the_warm_rounds_and_measured_pods_in_the_window():
    config = _fixture()
    config["pod_templates"][0]["in"] = ["warm"]
    config["pod_templates"][2]["in"] = ["window"]
    templates = dep.pod_templates(config)
    warm = dep.pod_plan(templates, "warm", 4, 900)
    window = dep.pod_plan(templates, "window", 4, 900)
    assert collections.Counter(warm) == {0: 666, 1: 333}      # 6 : 3
    assert collections.Counter(window) == {1: 750, 2: 250}    # 3 : 1
    factory = feeder.PodFactory(templates, {"warm": warm, "window": window},
                                4)
    made = [factory.make("warm")[0] for _ in range(5)] + \
        [factory.make("window")[0] for _ in range(5)]
    assert made == warm[:5] + window[:5]
    # a closed loop's plan comes round again
    assert [factory.make("warm")[0] for _ in range(999)][-5:] == warm[:5]
    for bad in ({"in": []}, {"in": ["drain"]}, {"weight": 0},
                {"weight": 1.5}):
        config["pod_templates"][1].update(bad)
        with pytest.raises(dep.ConfigError):
            dep.pod_templates(config)
        config["pod_templates"][1].update({"in": ["warm"], "weight": 3})
    config["pod_templates"][1]["in"] = ["window"]
    config["pod_templates"][0]["in"] = ["window"]
    with pytest.raises(dep.ConfigError):
        dep.pod_plan(dep.pod_templates(config), "warm", 1, 1)


def test_counts_have_to_make_up_the_nodes_and_names_come_once():
    config = _fixture()
    config["nodes"] = 4999
    with pytest.raises(dep.ConfigError):
        dep.node_templates(config)
    config = _fixture()
    config["node_templates"][1]["name"] = "small"
    with pytest.raises(dep.ConfigError):
        dep.node_templates(config)
    config = _fixture()
    config["pod_templates"][1]["name"] = "small"
    with pytest.raises(dep.ConfigError):
        dep.pod_templates(config)
    assert dep.services({"namespace": "d", "services": 0}) == []
    assert dep.pod_templates(_fixture())[0]["namespace"] == "default"


def test_the_summary_reports_the_mix_that_was_offered():
    pods, order = {}, []
    for i in range(10):
        pods[f"p{i}"] = {"phase": "window", "template": i % 3,
                         "due_t": None, "sent_t": 100.0 + i,
                         "created_t": 100.1 + i, "bound_t": 100.2 + i,
                         "host": "n"}
        order.append(f"p{i}")
    del pods["p4"]["bound_t"]                        # a 'zoned' one
    s = feeder.summarize(pods, order, 100.0, 200.0, "closed",
                         ("small", "zoned", "ported"))
    assert s["by_template"] == {"small": {"attempted": 4, "bound": 4},
                                "zoned": {"attempted": 3, "bound": 2},
                                "ported": {"attempted": 3, "bound": 3}}
    assert s["attempted"] == 10 and s["failed"] == 1


def _listed(capacity, pod_limits, host_ports=()):
    from kubernetes_tpu.api import types as api
    from kubernetes_tpu.api.quantity import Quantity
    node = api.Node(metadata=api.ObjectMeta(name="n"), spec=api.NodeSpec(
        capacity={k: Quantity(v) for k, v in capacity.items()}))
    pods = []
    for i, limits in enumerate(pod_limits):
        pod = api.Pod(
            metadata=api.ObjectMeta(name=f"p{i}", namespace="default"),
            spec=api.PodSpec(host="n", containers=[api.Container(
                name="c", image="i",
                ports=[api.ContainerPort(host_port=p, container_port=p)
                       for p in host_ports],
                resources=api.ResourceRequirements(limits={
                    k: Quantity(v) for k, v in limits.items()}))]))
        pod.status.host = "n"
        pods.append(pod)
    return cpl.check_final_list(pods, [node])


def test_the_final_list_sums_every_resource_a_node_states():
    fits = {"cpu": "1", "memory": "1Gi", "example.com/gpu": "1"}
    capacity = {"cpu": "4", "memory": "32Gi", "example.com/gpu": "2"}
    assert _listed(capacity, [fits, fits])["nodes_over_capacity"] == 0
    listed = _listed(capacity, [fits, fits, fits])       # cpu, memory fit
    assert listed["nodes_over_capacity"] == 1
    assert listed["max_cpu_share"] == pytest.approx(0.75)
    assert _listed({"cpu": "4", "memory": "1Gi"},
                   [{"cpu": "1", "memory": "600Mi"}] * 2
                   )["nodes_over_capacity"] == 1
    assert _listed(capacity, [fits, fits], host_ports=[80]
                   )["host_port_clashes"] == 1
