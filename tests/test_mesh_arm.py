"""``batch_solver.solve``'s mesh arm on a 1x4 mesh of the suite's virtual
devices, at a cluster past the Pallas kernel's domain (N > 32,640): the
decisions against the benchmark's plain reference and the one-device scan,
the spans and counters the arm keeps, and the prewarm's compile.
"""

import numpy as np
import pytest

import jax

from benchmarks.references import serial_resources as ref
from kubernetes_tpu.api import types as api
from kubernetes_tpu.api.quantity import Quantity
from kubernetes_tpu.models import batch_solver as bs
from kubernetes_tpu.models.policy import BatchPolicy
from kubernetes_tpu.models.snapshot import encode_snapshot
from kubernetes_tpu.ops import pallas_solver
from kubernetes_tpu.parallel import mesh as pmesh

N_NODES = 32_770          # past _MAX_N, and not a multiple of four: padded
WAVE = 16                 # one pod-axis bucket for every wave
REQUEST = (100, 500 * 2 ** 20)
SOLVE_PARTS = ("solve.hostprep", "solve.route", "solve.ship",
               "solve.launch", "solve.readback", "solve.post")


def _nodes(n):
    return [api.Node(metadata=api.ObjectMeta(name=f"node-{i:05d}"),
                     spec=api.NodeSpec(capacity={"cpu": Quantity("4"),
                                                 "memory": Quantity("32Gi")}))
            for i in range(n)]


def _pods(first, count):
    return [api.Pod(
        metadata=api.ObjectMeta(name=f"pod-{i:05d}", namespace="default",
                                uid=f"uid-{i:05d}"),
        spec=api.PodSpec(containers=[api.Container(
            name="pause", image="pause", resources=api.ResourceRequirements(
                limits={"cpu": Quantity("100m"),
                        "memory": Quantity("500Mi")}))]))
        for i in range(first, first + count)]


@pytest.fixture(scope="module")
def mesh():
    assert pallas_solver._MAX_N < N_NODES
    return pmesh.make_mesh(jax.devices()[:4])


@pytest.fixture(scope="module")
def big_nodes():
    return _nodes(N_NODES)


def _part_counts():
    return {p: bs.wave_parts().count(p) for p in SOLVE_PARTS}


def test_three_waves_equal_the_reference_and_the_one_device_scan(
        mesh, big_nodes):
    cluster = ref.Cluster({n.metadata.name: (4000, 32 * 2 ** 30)
                           for n in big_nodes})
    programs0, parts0 = bs.wave_programs().by_label(), _part_counts()
    placed0 = bs.mesh_placed_bytes().total()
    existing = []
    for w in range(3):
        pending = _pods(w * WAVE, WAVE)
        snap = encode_snapshot(big_nodes, existing, pending, [])
        chosen, scores = bs.solve(snap, mesh=mesh)
        want = ref.solve_wave(cluster, [(p.metadata.uid, REQUEST)
                                        for p in pending])
        hosts = bs.decisions_to_names(snap, chosen)
        assert [(h, int(s)) for h, s in zip(hosts, scores)] == want
        one_chosen, one_scores = bs.solve_jit(
            bs.ship_inputs(bs.snapshot_to_host_inputs(snap)))
        assert np.array_equal(chosen, np.asarray(one_chosen))
        assert np.array_equal(scores, np.asarray(one_scores))
        for pod, host in zip(pending, hosts):     # the commit between waves
            pod.spec.host = pod.status.host = host
        existing += pending

    # once a wave: the program, the bytes, each of the six parts
    grown = {k: n - programs0.get(k, 0)
             for k, n in bs.wave_programs().by_label().items()
             if n - programs0.get(k, 0)}
    assert grown == {("scan-sharded", "cpu"): 3}
    assert bs.mesh_placed_bytes().total() - placed0 > 3 * N_NODES * 4
    assert {p: n - parts0[p] for p, n in _part_counts().items()} == \
        dict.fromkeys(SOLVE_PARTS, 3)


def test_a_kernel_eligible_wave_under_the_mesh_counts_as_before(mesh):
    snap = encode_snapshot(_nodes(5_000), [], _pods(0, 4), [])
    programs0 = bs.wave_programs().by_label()
    placed0 = bs.mesh_placed_bytes().total()
    chosen, _scores = bs.solve(snap, mesh=mesh)
    assert (chosen >= 0).all()
    grown = {k for k, n in bs.wave_programs().by_label().items()
             if n - programs0.get(k, 0)}
    assert len(grown) == 1 and grown <= {("pallas", "cpu"), ("scan", "cpu")}
    # the bytes that crossed are counted on the one-device arm too: with
    # nothing resident, every plane, unpadded
    assert bs.mesh_placed_bytes().total() - placed0 == sum(
        a.nbytes for a in bs.snapshot_to_host_inputs(snap))


def test_placed_bytes_are_the_bytes_that_crossed(mesh, big_nodes):
    """With resident planes the first wave places everything and the
    second ships its pod planes and the rows the first one's binds
    touched: O(rows + pod planes), not O(nodes)."""
    from kubernetes_tpu.models.incremental import IncrementalEncoder
    from kubernetes_tpu.models.resident import ResidentPlanes
    enc, planes = IncrementalEncoder(), ResidentPlanes()
    bound, crossed = [], []
    for w in range(3):
        pending = _pods(900 + w * WAVE, WAVE)
        snap = enc.encode_delta(big_nodes, bound[-WAVE:], [], pending, []) \
            if w else enc.encode(big_nodes, bound, pending, [])
        placed0 = bs.mesh_placed_bytes().total()
        chosen, _scores = bs.solve(snap, mesh=mesh, resident=planes)
        crossed.append(bs.mesh_placed_bytes().total() - placed0)
        for pod, host in zip(pending, bs.decisions_to_names(snap, chosen)):
            pod.spec.host = pod.status.host = host
        bound += pending
    cold = bs.snapshot_to_host_inputs(snap)
    # no pod here has a service: the group rows are zeros made on the
    # device and never cross
    whole = sum(a.nbytes for a in cold) - cold.group_counts.nbytes
    assert crossed[0] > 0.9 * whole > N_NODES * 4
    # the second wave placed whole once more (the first binds grew the
    # band column: a new epoch); from the third on only what changed
    assert crossed[1] > 0.9 * whole
    assert crossed[2] < 64 * 1024 < whole / 20


def test_warm_compile_leaves_nothing_to_compile_for_a_wave_of_its_bucket(
        mesh, big_nodes):
    snap = encode_snapshot(big_nodes, [], _pods(0, 2), [])   # bucket P = 2
    host = bs.snapshot_to_host_inputs(snap)
    program = pmesh.sharded_program(mesh, snap.policy or BatchPolicy(),
                                    False, donate=False)
    before = program._cache_size()
    bs.warm_compile(host, snap.policy, False, bs.peer_bound_of(snap),
                    mesh=mesh)
    assert program._cache_size() == before + 1
    bs.solve(snap, host=host, mesh=mesh)
    assert program._cache_size() == before + 1
