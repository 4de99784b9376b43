"""``sched-load-5000n`` (benchmarks/configs/) on the CPU: the Kubernetes
scalability load test's mix — services of 5, 30 and 250 pods, one a group,
in namespaces of their own — as the harness reads the file, and the file's
own templates cut by count (200 nodes, three namespaces) sent wave after wave
down the path the wave loop's ``_solve_snap`` takes: ``IncrementalEncoder.
encode_delta``, the resident planes, ``batch_solver.solve`` on the XLA scan
and on the Pallas kernel through the interpreter. Host and score have to
equal the plain reference's (``benchmarks/references/serial_default.py``)
pod by pod.

Then what the deployment forced of the program, piece by piece: the service
index against the dense match it replaced; the sparse peer counts under
binds and deletes; a group row that sits out a wave; a wave with more
groups than one mask lane holds, and one with more than the kernel's cap;
the wave loop's cut at that cap; the group axis as a function of the pod
bucket; the two counters."""

import collections
import json
import os
import random

import jax
import numpy as np
import pytest

from benchmarks.harness import deployment as dep
from benchmarks.references import serial_default as ref
from kubernetes_tpu.api import types as api
from kubernetes_tpu.api.quantity import Quantity
from kubernetes_tpu.models import batch_solver as bs
from kubernetes_tpu.models import incremental
from kubernetes_tpu.models import resident as rs
from kubernetes_tpu.models.policy import BatchPolicy
from kubernetes_tpu.models.snapshot import encode_snapshot
from kubernetes_tpu.ops import pallas_solver
from kubernetes_tpu.scheduler import tpu_batch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAMESPACES = ("load-00", "load-01", "load-02")
HELD = {"big": 1, "medium": 4, "small": 120}    # groups kept, by class


def _file():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "sched-load-5000n.json")) as f:
        return json.load(f)


def _cut(nodes=200, held=HELD):
    """The file's own templates and services, cut by count: ``nodes`` nodes,
    the first groups of each class that live in the first three
    namespaces."""
    config = _file()
    config["nodes"] = config["node_templates"][0]["count"] = nodes
    left = dict(held)
    names = set()
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


def _api_node(name, t):
    return api.Node(
        metadata=api.ObjectMeta(name=name, labels=dict(t["labels"])),
        spec=api.NodeSpec(capacity={k: Quantity(v)
                                    for k, v in t["capacity"].items()}))


def _api_pod(uid, t, host=""):
    return api.Pod(
        metadata=api.ObjectMeta(name=uid, namespace=t["namespace"], uid=uid,
                                labels=dict(t["labels"])),
        spec=api.PodSpec(
            host=host, node_selector=dict(t["node_selector"]),
            containers=[api.Container(
                name=t["container"], image=t["image"],
                resources=api.ResourceRequirements(limits={
                    k: Quantity(v) for k, v in t["limits"].items()}))]),
        status=api.PodStatus(host=host))


def _api_services(services):
    return [api.Service(
        metadata=api.ObjectMeta(name=s["name"], namespace=s["namespace"]),
        spec=api.ServiceSpec(port=80, selector=dict(s["selector"])))
        for s in services]


def _waves(templates, seed, count, sizes=(1, 3, 8, 20, 90, 90)):
    rng = random.Random(seed)
    plan = dep.pod_plan(templates, "window", seed, count)[:count]
    pods = [(f"uid-{seed}-{i:04d}", templates[t]) for i, t in enumerate(plan)]
    waves, at = [], 0
    while at < len(pods):
        size = rng.choice(sizes)
        waves.append(pods[at:at + size])
        at += size
    return waves


class WarmPath:
    """The program's side, as the wave loop drives it: one encoder, one set
    of resident planes, each wave through ``encode_delta`` with the binds
    of the wave before as its upserts."""

    def __init__(self, nodes, services):
        self.nodes = [_api_node(n, t) for n, t in sorted(nodes.items())]
        self.services = _api_services(services)
        self.enc = incremental.IncrementalEncoder()
        self.planes = rs.ResidentPlanes()
        self.bound, self.upserted, self.removed = [], [], []
        self.snap = None

    def wave(self, wave):
        pending = [_api_pod(uid, t) for uid, t in wave]
        snap = self.enc.encode_delta(self.nodes, self.upserted, self.removed,
                                     pending, self.services)
        if snap is None:               # the first wave: nothing is held yet
            snap = self.enc.encode(self.nodes, self.bound, pending,
                                   self.services)
        self.upserted, self.removed, self.snap = [], [], snap
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


def _run(seed, count, program=None, reference=None, sizes=None):
    """-> (pods whose host or score differ, pods bound, pods the control
    places elsewhere, the most groups a wave named, the programs the waves
    took)."""
    sides = []
    for edit in (program, reference):
        config = _cut()
        if edit:
            edit(config)
        sides.append((dep.nodes_of(config), dep.pod_templates(config),
                      dep.services(config)))
    (p_nodes, p_templates, p_services), (r_nodes, r_templates, r_services) \
        = sides
    warm = WarmPath(p_nodes, p_services)
    cluster = ref.Cluster(r_nodes, r_services)
    control = ref.Cluster(r_nodes, r_services)
    programs0 = dict(bs.wave_programs().by_label())
    differ = elsewhere = bound = most = 0
    kw = {"sizes": sizes} if sizes else {}
    for p_wave, r_wave in zip(_waves(p_templates, seed, count, **kw),
                              _waves(r_templates, seed, count, **kw)):
        got = warm.wave(p_wave)
        want = ref.solve_wave(cluster, r_wave)
        differ += sum(g != w for g, w in zip(got, want))
        elsewhere += sum(
            c[0] != w[0] for c, w in
            zip(ref.solve_wave_uncommitted(control, r_wave), want))
        bound += sum(h is not None for h, _s in got)
        most = max(most, len(set(warm.snap.pod_gid[:len(p_wave)])))
    programs = {k[0] for k, n in bs.wave_programs().by_label().items()
                if n - programs0.get(k, 0)}
    return differ, bound, elsewhere, most, programs


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("program", ["scan", "pallas"])
def test_the_warm_path_decides_host_and_score_as_serial_default_does(
        program, seed, monkeypatch):
    """Every pod a member of a service, the spread term deciding among
    LeastRequested's ties from a group's second pod on; waves of up to 90
    pods name up to ~50 groups, past one mask lane."""
    monkeypatch.setenv("KTPU_PALLAS",
                       "interpret" if program == "pallas" else "off")
    differ, bound, elsewhere, most, programs = _run(
        2 ** 31 + seed, 360 if program == "scan" else 200)
    assert differ == 0
    assert programs == {program}
    assert bound == (360 if program == "scan" else 200)
    assert most > 31
    assert elsewhere >= 20         # the control: the in-wave commit put off


def _no_services(config):
    config["services"] = []


def _one_service_less(config):
    config["services"] = [s for s in config["services"]
                          if not s["name"].startswith("load-big-00000")]


def _one_service_more(config):
    # a second namespace's twin of a big group's selector: selects nobody
    # there, until the pods of that group are moved into it
    big = config["pod_templates"][0]
    for t in config["pod_templates"][:3]:
        t["labels"] = dict(big["labels"])
        t["namespace"] = big["namespace"]


@pytest.mark.parametrize("side", ["program", "reference"])
@pytest.mark.parametrize("edit", [_no_services, _one_service_less,
                                  _one_service_more])
def test_a_service_added_or_dropped_on_one_side_shows(edit, side):
    differ = _run(2 ** 31 + 77, 300, **{side: edit})[0]
    assert differ >= 1
    assert _run(2 ** 31 + 77, 300)[0] == 0


# -- the file, as the harness and the reference read it -----------------------

def test_the_file_states_the_load_test_s_mix_and_its_cut():
    config = _file()
    [pool] = dep.node_templates(config)
    assert pool["count"] == config["nodes"] == 5000
    assert pool["capacity"] == {"cpu": "4", "memory": "32Gi"}
    templates, services = dep.pod_templates(config), dep.services(config)
    sizes = collections.Counter(t["weight"] for t in templates)
    assert sizes == {5: 3600, 30: 300, 250: 36}
    pods = {w: w * n for w, n in sizes.items()}
    assert pods == {5: 18000, 30: 9000, 250: 9000}      # half, quarter, quarter
    assert len(services) == len(templates) == 3936
    assert sorted({t["namespace"] for t in templates}) == \
        [f"load-{i:02d}" for i in range(50)]
    for t, s in zip(templates, services):
        assert t["labels"] == s["selector"] == {"name": t["name"]}
        assert t["namespace"] == s["namespace"] and s["name"] == t["name"]
        assert t["limits"] == {"cpu": "10m", "memory": "26214400"}
        assert t["in"] == ["warm", "window"] and not t["node_selector"]
    assert "load.go" in config["source"] and "30 pods per node" in \
        config["source"] and len(config["source"]) <= 200
    assert config["source_sizes"]["pods"] == 150_000
    assert config["source_sizes"]["services"] == 16_400
    assert config["held_sizes"]["pods"] == 36_000
    assert config["reduced"] == ["measured_pods", "init_pods", "groups"]
    assert config["reference"] == "serial_default"
    assert config["kernel_program"] == "pallas"
    for key in ("recalled", "node_shape", "limits_as_requests",
                "plan_not_batches", "services_first", "services_cut"):
        assert config["assumed"][key]


def test_the_file_shares_nodes_scheduler_and_pins_with_its_control():
    config = _file()
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "sched-basic-5000n.json")) as f:
        basic = json.load(f)
    [pool], [b_pool] = dep.node_templates(config), dep.node_templates(basic)
    assert dict(pool, name="") == dict(b_pool, name="")
    for key in ("nodes", "scheduler", "env", "kernel_program",
                "measured_pods"):
        assert config[key] == basic[key], key


# -- the service index ---------------------------------------------------------

def _dense_match(services, namespace, labels):
    """The rule the index replaced, service by service."""
    return tuple(
        i for i, s in enumerate(services)
        if s.spec.selector
        and (not s.metadata.namespace or s.metadata.namespace == namespace)
        and all((labels or {}).get(k) == v
                for k, v in s.spec.selector.items()))


@pytest.mark.parametrize("seed", range(6))
def test_the_service_index_finds_what_the_dense_match_finds(seed):
    rng = random.Random(seed)
    keys, values = ["app", "tier", "name", "env"], ["a", "b", "c"]
    spaces = ["", "ns-1", "ns-2"]
    services = []
    for i in range(40):
        selector = {k: rng.choice(values)
                    for k in rng.sample(keys, rng.randrange(0, 4))}
        services.append(api.Service(
            metadata=api.ObjectMeta(name=f"s{i}",
                                    namespace=rng.choice(spaces)),
            spec=api.ServiceSpec(port=80, selector=selector)))
    index = incremental._ServiceIndex(services)
    hits = 0
    for _ in range(300):
        labels = {k: rng.choice(values)
                  for k in rng.sample(keys, rng.randrange(0, 5))} or None
        namespace = rng.choice(spaces[1:])
        want = _dense_match(services, namespace, labels)
        assert index.match(namespace, labels) == want
        hits += bool(want)
    assert hits > 50


def test_two_services_that_select_one_pod_both_count_it():
    """Multi-membership, as the full encoder's member matrix has it: the
    pod's own row is its FIRST service's, its commit counts toward both."""
    nodes = [_api_node(f"n{i}", {"labels": {}, "capacity":
                                 {"cpu": "4", "memory": "8Gi"}})
             for i in range(4)]
    services = _api_services([
        {"name": "by-app", "namespace": "d", "selector": {"app": "x"}},
        {"name": "by-tier", "namespace": "d", "selector": {"tier": "t"}}])
    t = {"namespace": "d", "labels": {"app": "x", "tier": "t"},
         "node_selector": {}, "container": "c", "image": "i",
         "limits": {"cpu": "100m", "memory": "1Mi"}}
    only_tier = dict(t, labels={"tier": "t"})
    enc = incremental.IncrementalEncoder()
    pending = [_api_pod("both", t), _api_pod("tier", only_tier)]
    snap = enc.encode(nodes, [], pending, services)
    full = encode_snapshot(nodes, [], pending, services)
    assert list(snap.pod_gid[:2]) == list(full.pod_gid[:2]) == [0, 1]
    assert snap.pod_group_member[0, :2].tolist() == [True, True]
    assert snap.pod_group_member[1, :2].tolist() == [False, True]
    assert np.array_equal(bs.solve(snap)[0][:2], bs.solve(full)[0][:2])


# -- the sparse peer counts ----------------------------------------------------

def _small_cluster():
    config = _cut(nodes=12, held={"big": 1, "medium": 1, "small": 3})
    nodes = [_api_node(n, t)
             for n, t in sorted(dep.nodes_of(config).items())]
    return nodes, dep.pod_templates(config), \
        _api_services(dep.services(config))


def test_a_bind_raises_and_a_delete_lowers_the_sparse_counts():
    nodes, templates, services = _small_cluster()
    big = templates[0]
    enc = incremental.IncrementalEncoder()
    enc.encode(nodes, [], [], services)
    bound = [_api_pod(f"b{i}", big, host=nodes[i % 3].metadata.name)
             for i in range(7)]
    enc.encode_delta(nodes, bound, [], [], services)
    [(key, at)] = enc._peers.items()
    assert sorted(at.items()) == [(0, 3), (1, 2), (2, 2)]
    epoch = enc._epoch
    enc.encode_delta(nodes, [], bound[:4], [], services)
    assert sorted(enc._peers[key].items()) == [(0, 1), (1, 1), (2, 1)]
    enc.encode_delta(nodes, [], bound[4:], [], services)
    assert enc._peers == {}                 # a group without peers goes
    assert enc._epoch == epoch              # and none of it ends an epoch


def test_a_row_left_out_of_one_wave_and_back_in_a_later_one_counts_the_same():
    nodes, templates, services = _small_cluster()
    big, medium = templates[0], templates[1]
    enc = incremental.IncrementalEncoder()
    enc.encode(nodes, [], [], services)
    bound = [_api_pod(f"b{i}", big, host=nodes[i % 5].metadata.name)
             for i in range(9)]
    first = enc.encode_delta(nodes, bound, [], [_api_pod("p1", big)],
                             services)
    away = enc.encode_delta(nodes, [], [], [_api_pod("p2", medium)],
                            services)
    back = enc.encode_delta(nodes, [], [], [_api_pod("p3", medium),
                                            _api_pod("p4", big)], services)
    assert first.pod_gid[0] == 0 and back.pod_gid[1] == 1
    assert np.array_equal(first.group_counts[0], back.group_counts[1])
    assert first.group_counts[0].sum() == 9
    assert not away.group_counts.any() and not back.group_counts[0].any()
    assert first.resident_epoch == away.resident_epoch == back.resident_epoch


def test_a_changed_service_set_is_indexed_again_and_every_pod_matched_anew():
    nodes, templates, services = _small_cluster()
    big = templates[0]
    enc = incremental.IncrementalEncoder()
    bound = [_api_pod(f"b{i}", big, host=nodes[i].metadata.name)
             for i in range(4)]
    enc.encode(nodes, bound, [], services)
    rebuilds = enc.op_counts["node_rebuilds"]
    assert sum(sum(at.values()) for at in enc._peers.values()) == 4
    snap = enc.encode_delta(nodes, [], [], [_api_pod("p", big)], services[1:])
    assert snap is not None and snap.pod_gid[0] == -1 and enc._peers == {}
    snap = enc.encode_delta(nodes, [], [], [_api_pod("q", big)], services)
    assert snap.pod_gid[0] == 0 and snap.group_counts[0].sum() == 4
    assert enc.op_counts["node_rebuilds"] == rebuilds


def test_the_same_service_objects_are_not_fingerprinted_again(monkeypatch):
    nodes, templates, services = _small_cluster()
    enc = incremental.IncrementalEncoder()
    enc.encode(nodes, [], [_api_pod("p", templates[0])], services)
    calls = []
    monkeypatch.setattr(enc, "_svc_fp",
                        lambda s: calls.append(s) or ("", "", ()))
    for i in range(3):
        enc.encode_delta(nodes, [], [], [_api_pod(f"q{i}", templates[0])],
                         list(services))
    assert calls == []


# -- the group axis, the cap and the cut ---------------------------------------

def _many_groups(n_groups, n_nodes=24, per_group=1):
    """``n_groups`` services of one namespace, ``per_group`` pending pods
    each, one bound peer each."""
    nodes = [_api_node(f"n{i:03d}", {"labels": {}, "capacity":
                                     {"cpu": "64", "memory": "64Gi"}})
             for i in range(n_nodes)]
    services = _api_services([
        {"name": f"g{g:03d}", "namespace": "d",
         "selector": {"name": f"g{g:03d}"}} for g in range(n_groups)])

    def pod(uid, g, host=""):
        return _api_pod(uid, {
            "namespace": "d", "labels": {"name": f"g{g:03d}"},
            "node_selector": {}, "container": "c", "image": "i",
            "limits": {"cpu": "10m", "memory": "1Mi"}}, host=host)

    bound = [pod(f"b{g}", g, host=nodes[g % n_nodes].metadata.name)
             for g in range(n_groups)]
    pending = [pod(f"p{g}-{k}", g) for k in range(per_group)
               for g in range(n_groups)]
    return nodes, services, bound, pending


@pytest.mark.parametrize("n_groups,eligible", [(40, True), (256, True),
                                               (300, False)])
def test_a_wave_past_one_mask_lane_and_one_past_the_cap(n_groups, eligible):
    """More than 31 groups ride further mask lanes on the kernel; more than
    the cap's leave it for the scan. Both decide what the oracle's full
    encode decides, and the kernel what the scan does."""
    nodes, services, bound, pending = _many_groups(n_groups)
    enc = incremental.IncrementalEncoder()
    snap = enc.encode(nodes, bound, pending, services)
    full = encode_snapshot(nodes, bound, pending, services)
    assert len(set(snap.pod_gid[:len(pending)])) == n_groups
    G = snap.group_counts.shape[0]
    assert G == {40: 64, 256: 256, 300: 512}[n_groups]   # the pod bucket
    inp = bs.snapshot_to_inputs(snap)
    assert pallas_solver.eligible(inp, BatchPolicy(), False,
                                  bs.peer_bound_of(snap)) is eligible
    chosen, scores = bs.solve_jit(inp, pol=BatchPolicy(), gangs=False)
    want, want_scores = bs.solve(full)
    P = len(pending)
    assert np.array_equal(np.asarray(chosen)[:P], want[:P])
    assert np.array_equal(np.asarray(scores)[:P], want_scores[:P])
    if eligible and n_groups <= 64:      # the interpreter is slow past that
        k_chosen, k_scores = pallas_solver.solve_pallas(
            inp, pol=BatchPolicy(), interpret=True)
        assert np.array_equal(np.asarray(k_chosen), np.asarray(chosen))
        assert np.array_equal(np.asarray(k_scores), np.asarray(scores))


def test_the_group_axis_follows_the_pod_bucket_once_a_wave_passes_the_floor():
    nodes, services, bound, pending = _many_groups(40)
    enc = incremental.IncrementalEncoder()
    shapes = []
    for take in (3, 8, 9, 40, 5, 20):
        snap = enc.encode(nodes, bound, pending[:take], services)
        shapes.append((snap.req.shape[0], snap.group_counts.shape[0]))
    # the floor until a wave names nine; from then on the pod bucket
    assert shapes == [(4, 8), (8, 8), (16, 16), (64, 64), (8, 8), (32, 32)]
    assert "G" not in enc.fill_dims()
    narrow = incremental.IncrementalEncoder()
    narrow.encode(nodes, bound, pending[:5], services)
    assert narrow.fill_dims()["G"] == 5
    # and never past what the kernel takes at this cluster's size
    assert enc.group_cap() == pallas_solver.max_groups(24) == 256
    assert pallas_solver.max_groups(5000) == 256
    assert pallas_solver.max_groups(20_000) == 64


class _Config:
    """What ``BatchScheduler`` needs of a ``SchedulerConfig`` to cut and
    carry a wave."""

    def __init__(self, nodes, pods):
        self.queue = list(pods)
        self.minion_lister = type("L", (), {"list": lambda _s: api.NodeList(
            items=nodes)})()
        self.modeler = type("M", (), {"list": lambda _s: []})()
        self.recorder = None
        self.provider, self.policy = "DefaultProvider", None

    def next_pod(self, timeout=None):
        if not self.queue:
            raise TimeoutError
        return self.queue.pop(0)

    def error(self, pod, err):
        raise AssertionError(err)


def test_the_wave_loop_cuts_a_wave_at_the_cap_and_carries_the_rest(
        monkeypatch):
    """One rule for every deployment: 300 pods of 300 groups become a wave
    of 256 groups and a wave of 44, in order; without a service nothing is
    cut."""
    monkeypatch.setenv("KTPU_PREWARM", "off")
    nodes, services, _bound, pending = _many_groups(300)
    factory = type("F", (), {})()
    factory.service_store = type("S", (), {"list": lambda _s: services})()
    sched = tpu_batch.BatchScheduler(_Config(nodes, pending), factory, None)
    cuts = tpu_batch._wave_metrics().group_cuts
    before = cuts.value()
    first = sched._prepare_wave(sched._drain_wave(0.01))[0]
    assert [p.metadata.name for p in first] == \
        [p.metadata.name for p in pending[:256]]
    second = sched._prepare_wave(sched._drain_wave(0.01))[0]
    assert [p.metadata.name for p in second] == \
        [p.metadata.name for p in pending[256:]]
    assert cuts.value() - before == 1
    snap = sched._encode_wave(nodes, first, services, lambda: [])
    assert snap.group_counts.shape[0] == 256
    factory.service_store = type("S", (), {"list": lambda _s: []})()
    plain = tpu_batch.BatchScheduler(_Config(nodes, pending), factory, None)
    assert len(plain._prepare_wave(plain._drain_wave(0.01))[0]) == 300


# -- the encoder's account of a wave's groups ----------------------------------

def test_the_two_counters_and_the_span_read_what_a_seeded_wave_holds():
    from kubernetes_tpu.util import metrics
    nodes, services, bound, pending = _many_groups(12, per_group=2)
    enc = incremental.IncrementalEncoder()
    groups, peered = incremental.wave_groups(), incremental.peered_pods()
    parts = metrics.wave_parts()
    g0, p0, spans0 = groups.value(), peered.value(), \
        parts.count("encode.groups")
    enc.encode(nodes, bound[:5], pending, services)   # five groups peered
    assert groups.value() - g0 == 12
    assert peered.value() - p0 == 10
    assert parts.count("encode.groups") == spans0 + 1
    plain = [_api_pod(f"plain{i}", {
        "namespace": "d", "labels": {}, "node_selector": {},
        "container": "c", "image": "i",
        "limits": {"cpu": "10m", "memory": "1Mi"}}) for i in range(3)]
    g0, p0 = groups.value(), peered.value()
    enc.encode(nodes, bound[:5], plain, services)
    assert (groups.value() - g0, peered.value() - p0) == (0, 0)


def test_group_rows_ride_the_wave_and_no_wave_rebuilds_for_them():
    """The resident planes through waves whose group axis changes with
    the pod bucket: every wave after the first is patched, and the device's
    group rows are the snapshot's."""
    nodes, services, _bound, pending = _many_groups(40, n_nodes=400,
                                                    per_group=3)
    warm = WarmPath({n.metadata.name: {"labels": {}, "capacity": {
        "cpu": "64", "memory": "64Gi"}} for n in nodes}, [])
    warm.services = services
    before = dict(rs.resident_waves().by_label())
    at = 0
    for take in (2, 30, 9, 50, 4):
        wave = pending[at:at + take]
        at += take
        snap = warm.enc.encode_delta(warm.nodes, warm.upserted, [], wave,
                                     services) or \
            warm.enc.encode(warm.nodes, [], wave, services)
        host = warm.planes.host_inputs(snap)
        shipped = warm.planes.ship(host)
        assert shipped is not None
        assert np.array_equal(np.asarray(shipped[0].group_counts),
                              snap.group_counts)
        chosen, _ = bs.solve(snap, host=warm.planes.host_inputs(snap),
                             resident=warm.planes)
        warm.upserted = []
        for pod, h in zip(wave, bs.decisions_to_names(snap, chosen)):
            pod.spec.host = pod.status.host = h
            warm.upserted.append(pod)
    grown = {k: n - before.get(k, 0)
             for k, n in rs.resident_waves().by_label().items()
             if n - before.get(k, 0)}
    assert grown == {("rebuilt", "first"): 1, ("rebuilt", "column"): 1,
                     ("patched", ""): 8}


def test_jax_runs_on_the_cpu_here():
    assert jax.default_backend() == "cpu"
