"""The main path's device programs, compiled at deployment size for a v5e
that is described, not attached (on-chip-measurement guide, section 2).

Interpret-mode tests cannot see what the chip's compiler refuses: a slice
off the tiling, a kernel over its VMEM budget, a program that cannot be
partitioned. These compiles can, at no chip time. Nothing runs, so they
say nothing about results or speed — chip_smoke.py does that on the chip.

All in ONE file on purpose: the process that describes the topology loads
libtpu and keeps its lock until it exits, so a second file of these on
another xdist worker would skip in silence. The topology is described
inside a fixture (never at import), the compiles run in this process, and
the persistent compile cache is off around them (an entry written for a
described chip cannot be read back here and only warns).

The two slow north-star compiles — solve_jit at 5,000 x 10,000 (31 s) and
_unpack_device at 5,000 x 10,000 (67 s) — are left to chip_smoke.py,
which pays and reports them on the chip.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import bench
from kubernetes_tpu.models import batch_solver as bs
from kubernetes_tpu.models.policy import BatchPolicy, batch_policy_from
from kubernetes_tpu.models.snapshot import encode_snapshot
from kubernetes_tpu.ops import pallas_solver


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — whatever stops it: skip
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _wave(n_nodes, n_pods, policy=None, **cluster_kw):
    """(snapshot, host SolverInputs) of one bench.build_cluster wave."""
    nodes, existing, pending, services = bench.build_cluster(
        n_nodes, n_pods, **cluster_kw)
    pol = batch_policy_from(policy=policy) if policy else None
    snap = encode_snapshot(nodes, existing, pending, services, policy=pol)
    return snap, bs.snapshot_to_host_inputs(snap)


def _abstract(host, sharding, batch=None):
    """SolverInputs of shapes placed by ``sharding`` — one sharding for
    every plane, or a SolverInputs of per-plane shardings."""
    per = sharding if isinstance(sharding, bs.SolverInputs) \
        else [sharding] * len(host)
    lead = () if batch is None else (batch,)
    return bs.SolverInputs(*(
        jax.ShapeDtypeStruct(lead + a.shape, a.dtype, sharding=s)
        for a, s in zip(host, per)))


def _compile_kernel(snap, host, sharding):
    """Lower the Pallas kernel the way solve_pallas calls it: the inner
    jit under x64-off (an outer jit with x64 on is refused: 64-bit
    types), the tie-break hashes already split into int32 limbs."""
    pol = snap.policy or BatchPolicy()
    assert pallas_solver.eligible(host, pol, snap.has_gangs,
                                  bs.peer_bound_of(snap))
    inp = _abstract(host, sharding)
    limbs = jax.ShapeDtypeStruct((host.req.shape[0], 4), jnp.int32,
                                 sharding=sharding)
    with jax.enable_x64(False):
        compiled = pallas_solver._solve_pallas_x32.lower(
            *pallas_solver._x32_operands(inp, limbs), pol=pol,
            interpret=False, gangs=snap.has_gangs, B=1).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("n_nodes,n_pods,kw", [
    pytest.param(5_000, 10_000, {}, id="north_star"),
    pytest.param(2_000, 0, {"gang_groups": 1_000, "gang_size": 8},
                 id="gang"),
    pytest.param(32_000, 1_024, {}, id="kernel_edge"),
    # a wave of the load test's mix: as many group rows as the kernel takes
    # at 5,000 nodes, membership over nine mask lanes, the pod's own row
    # read by a dynamic index, the off-list peers from SMEM
    pytest.param(5_000, 256, {"n_services": 256}, id="group_rows"),
])
def test_kernel_compiles(one_chip, no_persistent_cache, n_nodes, n_pods, kw):
    snap, host = _wave(n_nodes, n_pods, **kw)
    assert snap.has_gangs == bool(kw.get("gang_groups"))
    if "n_services" in kw:
        assert host.group_counts.shape[0] == kw["n_services"] == \
            pallas_solver.max_groups(n_nodes)
    compiled = _compile_kernel(snap, host, one_chip)
    # the [P, NR, 128] int32 static mask dominates: it must fit HBM
    assert compiled.memory_analysis().temp_size_in_bytes < (8 << 30)


def test_kernel_compiles_affinity_policy(one_chip, no_persistent_cache):
    """Zone anti-affinity planes (bench's `affinity` config): V-deep
    reduction planes in VMEM beside the node state."""
    snap, host = _wave(5_000, 5_000, policy=bench.affinity_policy())
    assert snap.policy.anti_affinity
    _compile_kernel(snap, host, one_chip)


def test_scan_compiles_with_preempt_bands(one_chip, no_persistent_cache):
    """kube-preempt waves are outside the kernel's domain (eligible() is
    False for B > 0), so on the chip priority waves take the XLA scan."""
    nodes, existing, pending = bench.build_priority_cluster(2_000, 1_000)
    snap = encode_snapshot(nodes, existing, pending, [])
    host = bs.snapshot_to_host_inputs(snap)
    assert host.band_prio.shape[0] > 0
    assert not pallas_solver.eligible(host, snap.policy or BatchPolicy(),
                                      False, bs.peer_bound_of(snap))
    bs.solve_jit.lower(_abstract(host, one_chip), pol=snap.policy,
                       gangs=False).compile()


def test_solverd_coalesced_program_compiles(one_chip, no_persistent_cache):
    """kube-solverd's jit(vmap(solve_jit)) at one bucket a 5k-node cluster
    really produces: two workers' 1,024-pod waves, node axis pow-2 padded
    to 8,192 by the coalescer."""
    from kubernetes_tpu.solver import service
    snap, host = _wave(5_000, 1_024)
    target = service._target_dims([service._dims_of(host)])
    assert (target["N"], target["P"]) == (8_192, 1_024)
    padded = service._pad_inputs(host, target)
    fn = service._batched_solver(snap.policy or BatchPolicy(), False)
    fn.lower(_abstract(padded, one_chip, batch=2)).compile()


def test_sharded_scan_compiles_on_1x4_mesh(topo, no_persistent_cache):
    """Beyond the kernel's 32,640-node limit only the GSPMD scan over the
    mesh solves a wave: 40,960 nodes over four chips, collectives put in
    by the partitioner."""
    from kubernetes_tpu.parallel import mesh as pm
    snap, host = _wave(40_960, 1_024)
    pol = snap.policy or BatchPolicy()
    assert not pallas_solver.eligible(host, pol, False,
                                      bs.peer_bound_of(snap))
    mesh = pm.make_mesh(topo.devices, pods_axis=1)
    assert dict(mesh.shape) == {"pods": 1, "nodes": 4}
    inp = _abstract(host, pm.input_shardings(mesh))
    resident = tuple(getattr(inp, f) for f in pm.RESIDENT_FIELDS)
    wave = tuple(getattr(inp, f) for f in pm.WAVE_FIELDS)
    compiled = pm.sharded_program(mesh, pol, False, donate=False).lower(
        resident, wave).compile()
    text = compiled.as_text()
    assert "all-reduce" in text and "all-gather" in text


def test_unpack_program_compiles_at_served_bucket(one_chip,
                                                  no_persistent_cache):
    """The packed transfer's unpack program at one served wave bucket
    (5,000 nodes x 1,024 pods): a compile of its own per shape bucket."""
    _snap, host = _wave(5_000, 1_024)
    spec, total = bs._pack_spec(host)
    buf = jax.ShapeDtypeStruct((total,), jnp.uint8, sharding=one_chip)
    bs._unpack_device.lower(buf, spec=spec).compile()


def _apply_lowering(host, mesh, sharding_of, rows_bucket):
    """The resident planes' apply program (models/resident.py) of one pod
    and row bucket, lowered for planes placed by ``sharding_of(field)``."""
    import numpy as np
    from kubernetes_tpu.models import resident as rs
    from kubernetes_tpu.parallel import mesh as pm
    n = host.cap.shape[0]
    pad = 0 if mesh is None else pm._pad_width(n, mesh.shape["nodes"])
    planes = {f: getattr(host, f) for f in pm.RESIDENT_FIELDS}
    names = tuple(f for f in rs.PATCH_FIELDS if planes[f].size)
    spec, total = bs._pack_spec(rs.wave_arrays(
        host, names, np.zeros(1, np.int64), rows_bucket))
    patch = tuple(jax.ShapeDtypeStruct(
        pm.pad_plane(f, planes[f], pad).shape, planes[f].dtype,
        sharding=sharding_of(f)) for f in names)
    buf = jax.ShapeDtypeStruct((total,), jnp.uint8,
                               sharding=sharding_of(None))
    return rs._apply_program(spec, names, n, mesh, True).lower(patch, buf)


@pytest.mark.parametrize("n_pods", [128, 1_024])
def test_apply_program_compiles_at_served_bucket(one_chip,
                                                 no_persistent_cache, n_pods):
    """What the wave loop runs in ``_unpack_device``'s place: the pod
    planes and the dirty rows, patched into the planes the chip kept."""
    from kubernetes_tpu.models import resident as rs
    _snap, host = _wave(5_000, n_pods)
    for rows_bucket in rs.ROW_LADDER:
        _apply_lowering(host, None, lambda f: one_chip,
                        rows_bucket).compile()


def test_sharded_apply_program_compiles_without_a_collective(
        topo, no_persistent_cache):
    """On the 1x4 mesh the planes are patched where they lie: rows and
    pod planes arrive replicated, each chip writes the rows it owns."""
    from jax.sharding import NamedSharding, PartitionSpec
    from kubernetes_tpu.models import resident as rs
    from kubernetes_tpu.parallel import mesh as pm
    _snap, host = _wave(40_960, 256)
    mesh = pm.make_mesh(topo.devices, pods_axis=1)
    sh = pm.input_shardings(mesh)
    rep = NamedSharding(mesh, PartitionSpec())
    text = _apply_lowering(
        host, mesh, lambda f: rep if f is None else getattr(sh, f),
        rs.ROW_LADDER[-1]).compile().as_text()
    for collective in ("all-gather", "all-reduce", "collective-permute",
                       "all-to-all"):
        assert collective not in text, collective

