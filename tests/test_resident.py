"""The node planes the in-process solve keeps between waves
(models/resident.py): patched host and device forms against the cold
path's, every reason for a rebuild, and who may touch the live planes.
Every case runs on the one-device arm and on the mesh arm (a 1x4 mesh of
the suite's virtual devices; the mesh floor and the kernel's domain are
moved out of the way so that a small cluster takes it).
"""

import threading

import numpy as np
import pytest

import jax

from kubernetes_tpu.api import types as api
from kubernetes_tpu.api.quantity import Quantity
from kubernetes_tpu.models import batch_solver as bs
from kubernetes_tpu.models import resident as rs
from kubernetes_tpu.models.incremental import IncrementalEncoder
from kubernetes_tpu.models.snapshot import encode_snapshot
from kubernetes_tpu.ops import pallas_solver
from kubernetes_tpu.parallel import mesh as pmesh

N_NODES = 90              # not a multiple of four: the mesh arm pads


def _nodes(n=N_NODES):
    return [api.Node(metadata=api.ObjectMeta(name=f"node-{i:03d}"),
                     spec=api.NodeSpec(capacity={"cpu": Quantity("4"),
                                                 "memory": Quantity("32Gi")}))
            for i in range(n)]


def _pod(i, cpu="100m", memory="500Mi", **spec_kw):
    return api.Pod(
        metadata=api.ObjectMeta(name=f"pod-{i:05d}", namespace="default",
                                uid=f"uid-{i:05d}", labels={"app": "web"}),
        spec=api.PodSpec(containers=[api.Container(
            name="pause", image="pause", resources=api.ResourceRequirements(
                limits={"cpu": Quantity(cpu),
                        "memory": Quantity(memory)}))], **spec_kw))


@pytest.fixture(params=["one-device", "mesh"])
def mesh(request, monkeypatch):
    """None on the one-device arm; on the mesh arm a 1x4 mesh that every
    wave takes, whatever its size."""
    if request.param == "one-device":
        return None
    monkeypatch.setattr(pmesh, "DEFAULT_MESH_MIN_NODES", 0)
    monkeypatch.setattr(pallas_solver, "eligible", lambda *a, **kw: False)
    return pmesh.make_mesh(jax.devices()[:4])


class Cluster:
    """An encoder, its resident planes and the pods bound so far; a wave
    goes through ``encode_delta`` as the wave loop's does."""

    def __init__(self, mesh, nodes=None):
        self.mesh = mesh
        self.nodes = nodes or _nodes()
        self.services = []
        self.enc = IncrementalEncoder()
        self.planes = rs.ResidentPlanes()
        self.bound = {}
        self.upserted, self.removed = [], []
        self.next_pod = 0
        self.host = None

    def pods(self, count, **kw):
        out = [_pod(self.next_pod + i, **kw) for i in range(count)]
        self.next_pod += count
        return out

    def encode(self, pending, full=False):
        snap = None if full else self.enc.encode_delta(
            self.nodes, self.upserted, self.removed, pending, self.services)
        if snap is None:
            snap = self.enc.encode(self.nodes, list(self.bound.values()),
                                   pending, self.services)
        self.upserted, self.removed = [], []
        return snap

    def solve(self, snap):
        self.host = self.planes.host_inputs(snap)
        return bs.solve(snap, host=self.host, mesh=self.mesh,
                        resident=self.planes)

    def wave(self, pending, full=False):
        """Encode, solve through the resident planes, check the answer
        against a cold solve, commit the binds. -> the snapshot."""
        snap = self.encode(pending, full=full)
        chosen, scores = self.solve(snap)
        cold_chosen, cold_scores = bs.solve(snap, mesh=self.mesh)
        assert np.array_equal(chosen, cold_chosen)
        assert np.array_equal(scores, cold_scores)
        for pod, host in zip(pending, bs.decisions_to_names(snap, chosen)):
            if host is not None:
                pod.spec.host = pod.status.host = host
                self.bound[pod.metadata.uid] = pod
                self.upserted.append(pod)
        return snap

    def delete(self, pod):
        del self.bound[pod.metadata.uid]
        self.removed.append(pod)

    def move(self, pod, to):
        pod.spec.host = pod.status.host = to
        self.upserted.append(pod)


def _outcomes():
    return dict(rs.resident_waves().by_label())


def _grown(before):
    return {k: n - before.get(k, 0) for k, n in _outcomes().items()
            if n - before.get(k, 0)}


def _assert_device_equals_fresh(cluster, snap):
    """Every plane the device keeps against a fresh placement of the cold
    path's host planes (padded to the mesh on that arm)."""
    cold = bs.snapshot_to_host_inputs(snap)
    if cluster.mesh is not None:
        cold, _ = pmesh.pad_inputs_for_mesh(cold, cluster.mesh)
    dev = cluster.planes._dev
    kept = {**dev.static, **dev.patch}
    assert set(kept) == set(rs.STATIC_FIELDS + rs.PATCH_FIELDS)
    for f, a in kept.items():
        want = np.asarray(getattr(cold, f))
        assert a.dtype == want.dtype and np.array_equal(np.asarray(a), want), f
        if cluster.mesh is not None:
            assert a.sharding == getattr(
                pmesh.input_shardings(cluster.mesh), f), f


def test_twenty_waves_of_binds_deletes_and_a_move_stay_equal_to_the_cold_path(
        mesh):
    c = Cluster(mesh)
    before = _outcomes()
    for w in range(24):
        if w in (5, 11, 17):
            for pod in list(c.bound.values())[w:w + 3]:
                c.delete(pod)
        if w == 8:
            pod = next(iter(c.bound.values()))
            c.move(pod, next(n.metadata.name for n in c.nodes
                             if n.metadata.name != pod.spec.host))
        snap = c.wave(c.pods(1 + w % 7))
        cold = bs.snapshot_to_host_inputs(snap)
        for f, a, b in zip(c.host._fields, c.host, cold):
            assert a.dtype == b.dtype and a.shape == b.shape \
                and a.tobytes() == b.tobytes(), f
        _assert_device_equals_fresh(c, snap)
    grown = _grown(before)
    # the first wave, and the one after the first binds (they grow the
    # encoder's band column: a new epoch); every other wave is patched
    assert grown == {("rebuilt", "first"): 1, ("rebuilt", "column"): 1,
                     ("patched", ""): 22}


def _settled(mesh, nodes=None):
    """A cluster whose planes are resident and level: three waves in."""
    c = Cluster(mesh, nodes)
    for _ in range(3):
        c.wave(c.pods(4))
    return c


def _node_set_changed(c):
    c.nodes = c.nodes + [api.Node(
        metadata=api.ObjectMeta(name="node-zzz"),
        spec=api.NodeSpec(capacity={"cpu": Quantity("4"),
                                    "memory": Quantity("32Gi")}))]
    return c.pods(3)


def _service_changed(c):
    c.services = [api.Service(
        metadata=api.ObjectMeta(name="web", namespace="default"),
        spec=api.ServiceSpec(selector={"app": "db"}))]   # selects no pod
    return c.pods(3)


def _column_grown(c):
    return c.pods(2, node_selector={"disk": "ssd"})


def _restored(c):
    c.enc.restore(c.enc.checkpoint())
    return c.pods(3)


def _scale_shrinks(c):
    return c.pods(1, cpu="150m")      # the cpu scale is 100m until now


def _int32_to_int64(c):
    # a multiple of the scales, past what int32 holds times ten
    return c.pods(1, cpu="30000000")


REBUILDS = {
    "nodes": _node_set_changed,
    "column": _column_grown,
    "restore": _restored,
    "scale": _scale_shrinks,
    "dtype": _int32_to_int64,
}


@pytest.mark.parametrize("reason", sorted(REBUILDS))
def test_a_wave_that_cannot_be_patched_is_rebuilt_and_says_why(mesh, reason):
    c = _settled(mesh)
    before = _outcomes()
    snap = c.wave(REBUILDS[reason](c))
    assert _grown(before) == {("rebuilt", reason): 1}
    _assert_device_equals_fresh(c, snap)
    if reason == "dtype":             # and back to int32, whole once more
        before = _outcomes()
        c.wave(c.pods(2))
        assert _grown(before) == {("rebuilt", "dtype"): 1}
    before = _outcomes()
    c.wave(c.pods(2))                 # and the next is patched again
    assert _grown(before) == {("patched", ""): 1}


def test_a_changed_service_set_ends_no_epoch_and_the_wave_is_patched(mesh):
    """No node plane holds a service's peers (the group rows are the
    wave's own), so a service added, dropped or changed re-indexes the
    encoder and rebuilds nothing."""
    c = _settled(mesh)
    before = _outcomes()
    snap = c.wave(_service_changed(c))
    assert _grown(before) == {("patched", ""): 1}
    _assert_device_equals_fresh(c, snap)
    c.services = [api.Service(
        metadata=api.ObjectMeta(name="all", namespace="default"),
        spec=api.ServiceSpec(selector={"app": "web"}))]
    before = _outcomes()
    pending = c.pods(3)
    for p in pending:
        p.metadata.labels = {"app": "web"}
    snap = c.wave(pending)            # names a group: its rows are shipped
    assert _grown(before) == {("patched", ""): 1}
    assert (snap.pod_gid[:3] == 0).all()
    _assert_device_equals_fresh(c, snap)


def test_more_dirty_rows_than_the_planes_are_worth_places_whole(mesh):
    c = _settled(mesh, _nodes(8))
    c.wave(c.pods(8))                 # LeastRequested: a pod on every node
    before = _outcomes()
    snap = c.wave(c.pods(2))
    assert _grown(before) == {("rebuilt", "dirty_share"): 1}
    _assert_device_equals_fresh(c, snap)


def test_a_snapshot_of_the_full_encoder_is_never_patched_and_keeps_nothing(
        mesh):
    c = _settled(mesh)
    before = _outcomes()
    pending = c.pods(3)
    snap = encode_snapshot(c.nodes, list(c.bound.values()), pending, [])
    assert snap.resident_epoch is None
    chosen, scores = c.solve(snap)
    cold = bs.solve(snap, mesh=mesh)
    assert np.array_equal(chosen, cold[0]) and np.array_equal(scores, cold[1])
    assert _grown(before) == {("rebuilt", "no_epoch"): 1}
    before = _outcomes()
    c.wave(c.pods(2))
    assert _grown(before) == {("rebuilt", "first"): 1}


def test_warm_compile_on_another_thread_leaves_the_live_planes_alone(mesh):
    c = _settled(mesh)
    snap = c.encode(c.pods(2))
    host = bs.snapshot_to_host_inputs(snap)
    dev = c.planes._dev
    live = {f: (a, np.asarray(a).copy())
            for f, a in {**dev.static, **dev.patch}.items()}
    before = _outcomes()
    errors = []

    def warm():
        try:
            bs.warm_compile(host, snap.policy, False,
                            bs.peer_bound_of(snap), mesh=mesh)
        except Exception as e:  # noqa: BLE001 — re-raised below
            errors.append(e)

    t = threading.Thread(target=warm)
    t.start()
    t.join()
    assert not errors, errors
    assert _grown(before) == {}       # no live wave was counted
    for f, (a, was) in live.items():
        assert {**dev.static, **dev.patch}[f] is a
        assert not a.is_deleted() and np.array_equal(np.asarray(a), was), f
    before = _outcomes()
    c.wave(c.pods(3))                 # and they still take a patch
    assert _grown(before) == {("patched", ""): 1}


def test_warm_compile_leaves_the_apply_program_nothing_to_compile(
        mesh, monkeypatch):
    c = _settled(mesh)
    snap = c.encode(c.pods(2))
    programs, inner = set(), rs._apply_program
    monkeypatch.setattr(rs, "_apply_program",
                        lambda *a: programs.add(inner(*a)) or inner(*a))
    bs.warm_compile(bs.snapshot_to_host_inputs(snap), snap.policy, False,
                    bs.peer_bound_of(snap), mesh=mesh)
    warmed = {fn: fn._cache_size() for fn in programs}
    assert len(warmed) >= len(rs.ROW_LADDER) and all(warmed.values())
    c.solve(snap)
    assert {fn: fn._cache_size() for fn in programs} == warmed


def test_the_routers_host_route_leaves_the_device_planes_alone(monkeypatch):
    c = _settled(None)    # one device: a sharded wave never meets the router
    dev = c.planes._dev
    held = dict(dev.patch)
    cpu = jax.devices()[1]
    device_plan = bs.default_router.plan_for
    monkeypatch.setattr(
        bs.default_router, "plan_for",
        lambda *a, **kw: bs.WavePlan("host", cpu, 0.0, 0.0, 0.0))
    before = _outcomes()
    c.wave(c.pods(3))
    assert _grown(before) == {("bypassed", "host_route"): 1}
    assert all(dev.patch[f] is a and not a.is_deleted()
               for f, a in held.items())
    monkeypatch.setattr(bs.default_router, "plan_for", device_plan)
    before = _outcomes()
    snap = c.wave(c.pods(3))          # the rows of both waves, one patch
    assert _grown(before) == {("patched", ""): 1}
    _assert_device_equals_fresh(c, snap)


# -- the encoder's side: what it touched, by sequence ------------------------

def test_touched_rows_are_asked_for_by_sequence_not_by_build():
    """A snapshot that nobody applied (a solve that raised, a wave dropped
    at the gate) loses no row: the consumer asks for everything since the
    sequence of the snapshot it applied last."""
    c = _settled(None)
    pending = c.pods(4)
    first = c.wave(pending)
    on_first = {c.enc._node_index[p.spec.host] for p in pending}
    unseen = c.encode(c.pods(3))      # built, never applied
    assert sorted(set(unseen.touched_since(first.resident_seq))) == \
        sorted(on_first)
    extra = c.pods(2)
    for pod, node in zip(extra, c.nodes[-2:]):
        pod.spec.host = pod.status.host = node.metadata.name
        c.bound[pod.metadata.uid] = pod
        c.upserted.append(pod)
    third = c.encode(c.pods(1))
    assert third.resident_epoch == unseen.resident_epoch
    assert set(third.touched_since(first.resident_seq)) == \
        on_first | {N_NODES - 2, N_NODES - 1}
    assert third.touched_since(unseen.resident_seq) == \
        [N_NODES - 2, N_NODES - 1]
    # an older snapshot after a newer one, or one of an epoch gone by
    assert first.touched_since(third.resident_seq) is None
    c.enc.restore(c.enc.checkpoint())
    assert third.touched_since(first.resident_seq) is None


def test_a_trimmed_log_says_so(monkeypatch):
    from kubernetes_tpu.models import incremental
    monkeypatch.setattr(incremental, "_TOUCH_LOG_MAX", 8)
    c = _settled(None)
    first = c.wave(c.pods(2))
    second = c.wave(c.pods(2))
    for _ in range(4):
        last = c.wave(c.pods(4))
    assert last.resident_epoch == second.resident_epoch
    assert last.touched_since(second.resident_seq) is None
    assert last.touched_since(last.resident_seq) == []
    assert first.resident_seq < second.resident_seq < last.resident_seq
