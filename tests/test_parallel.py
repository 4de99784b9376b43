"""Multi-chip sharding tests on the 8-virtual-device CPU mesh.

The contract: sharding changes layout, never decisions — solve_sharded must
be bit-identical to single-device solve and to the serial oracle.
"""

import numpy as np
import pytest

import jax

from kubernetes_tpu.api import types as api
from kubernetes_tpu.api.quantity import Quantity
from kubernetes_tpu.models.batch_solver import (
    decisions_to_names,
    snapshot_to_inputs,
    solve,
)
from kubernetes_tpu.models.oracle import solve_serial
from kubernetes_tpu.models.snapshot import encode_snapshot
from kubernetes_tpu.parallel.mesh import make_mesh, pad_inputs_for_mesh, solve_sharded


def _cluster(n_nodes=13, n_pods=24):
    """Deliberately non-divisible node count: exercises mesh padding."""
    nodes = [api.Node(metadata=api.ObjectMeta(
        name=f"n{i}", labels={"zone": f"z{i % 3}"}),
        spec=api.NodeSpec(capacity={"cpu": Quantity("2"), "memory": Quantity("4Gi")}))
        for i in range(n_nodes)]
    svc = api.Service(metadata=api.ObjectMeta(name="web", namespace="default"),
                      spec=api.ServiceSpec(port=80, selector={"app": "web"}))
    pending = [api.Pod(
        metadata=api.ObjectMeta(name=f"p{i}", namespace="default",
                                uid=f"u{i}", labels={"app": "web"} if i % 2 else {}),
        spec=api.PodSpec(containers=[api.Container(
            name="c", image="i",
            resources=api.ResourceRequirements(limits={
                "cpu": Quantity("250m"), "memory": Quantity("256Mi")}))]))
        for i in range(n_pods)]
    return nodes, [], pending, [svc]


def test_mesh_has_eight_devices():
    assert len(jax.devices()) == 8, (
        "conftest must provide 8 virtual CPU devices for sharding tests")


def test_sharded_solve_bit_identical():
    nodes, existing, pending, services = _cluster()
    serial = solve_serial(nodes, existing, pending, services)
    snap = encode_snapshot(nodes, existing, pending, services)

    single, _ = solve(snap)
    mesh = make_mesh(pods_axis=1)  # 1x8: all devices shard the node axis
    sharded, _ = solve_sharded(snapshot_to_inputs(snap), mesh,
                               prefer_kernel=False)
    assert np.array_equal(single, sharded)
    assert decisions_to_names(snap, sharded) == serial


def test_sharded_2d_mesh():
    nodes, existing, pending, services = _cluster(n_nodes=16, n_pods=16)
    serial = solve_serial(nodes, existing, pending, services)
    snap = encode_snapshot(nodes, existing, pending, services)
    mesh = make_mesh(pods_axis=2)  # 2x4 mesh: dp over pods in the pre-pass
    sharded, _ = solve_sharded(snapshot_to_inputs(snap), mesh,
                               prefer_kernel=False)
    assert decisions_to_names(snap, sharded) == serial


def test_padding_nodes_never_win():
    nodes, existing, pending, services = _cluster(n_nodes=3, n_pods=40)
    snap = encode_snapshot(nodes, existing, pending, services)
    mesh = make_mesh(pods_axis=1)
    inp, n = pad_inputs_for_mesh(snapshot_to_inputs(snap), mesh)
    assert inp.cap.shape[0] == 8 and n == 3
    chosen, _ = solve_sharded(snapshot_to_inputs(snap), mesh,
                              prefer_kernel=False)
    assert chosen.max() < 3  # padding indices unreachable
    assert decisions_to_names(snap, chosen) == solve_serial(
        nodes, existing, pending, services)


def test_crossover_dispatch_runs_kernel_for_eligible_waves(monkeypatch):
    """solve_sharded's default dispatch: a kernel-eligible wave skips the
    sharded scan entirely and runs the Pallas sequential-commit kernel on
    one device (sharding buys capacity, not speed — see the measured
    numbers in solve_sharded's docstring). KTPU_PALLAS=interpret routes
    the kernel through the interpreter so the dispatch is testable on
    the CPU mesh."""
    from kubernetes_tpu.models.batch_solver import peer_bound_of
    from kubernetes_tpu.models.policy import BatchPolicy
    from kubernetes_tpu.ops import pallas_solver

    monkeypatch.setenv("KTPU_PALLAS", "interpret")
    nodes, existing, pending, services = _cluster()
    snap = encode_snapshot(nodes, existing, pending, services)
    inp = snapshot_to_inputs(snap)
    assert pallas_solver.eligible(inp, snap.policy or BatchPolicy(), False,
                                  peer_bound_of(snap))
    mesh = make_mesh(pods_axis=1)
    via_dispatch, _ = solve_sharded(inp, mesh)            # kernel route
    via_gspmd, _ = solve_sharded(inp, mesh, prefer_kernel=False)
    assert np.array_equal(via_dispatch, via_gspmd)
    assert decisions_to_names(snap, via_dispatch) == solve_serial(
        nodes, existing, pending, services)


def test_pad_width_memoized_and_padding_decision_invariant():
    """Satellite contract: pad widths come from the per-(N, shards) memo
    (no per-wave re-derivation) and the padded planes always pass the
    KTPU_DEBUG decision-invariance check — padding rows can never win a
    tie-break, advertise resources, or perturb zone counts."""
    from kubernetes_tpu.models.batch_solver import snapshot_to_host_inputs
    from kubernetes_tpu.parallel.mesh import (
        _assert_padding_invariant,
        _pad_width,
    )

    assert _pad_width(13, 8) == 3
    assert _pad_width(16, 8) == 0
    before = _pad_width.cache_info().hits
    assert _pad_width(13, 8) == 3
    assert _pad_width.cache_info().hits == before + 1

    nodes, existing, pending, services = _cluster(n_nodes=13)
    snap = encode_snapshot(nodes, existing, pending, services)
    inp = snapshot_to_host_inputs(snap)
    mesh = make_mesh(pods_axis=1)
    padded, n = pad_inputs_for_mesh(inp, mesh)
    # must not raise — every fill is decision-invariant by construction
    _assert_padding_invariant(padded, n)

    # a feasible padding row must be CAUGHT: corrupt one fill and the
    # debug gate has to fire (this is the assert that guards future
    # SolverInputs fields against silently feasible padding)
    bad = padded._replace(node_extra_ok=np.ones_like(
        np.asarray(padded.node_extra_ok)))
    with pytest.raises(AssertionError):
        _assert_padding_invariant(bad, n)


def test_sharded_at_partitioning_scale():
    """>=2k nodes over 8 devices: the node axis genuinely partitions
    (256+ rows per shard); sharded == unsharded == serial, and the
    memory report accounts the full plane set."""
    import numpy as np

    from kubernetes_tpu.models.batch_solver import solve_jit
    from kubernetes_tpu.parallel.mesh import shard_memory_report

    nodes, existing, pending, services = _cluster(n_nodes=2049, n_pods=64)
    snap = encode_snapshot(nodes, existing, pending, services)
    inp = snapshot_to_inputs(snap)
    mesh = make_mesh(pods_axis=1)
    chosen_sh, _ = solve_sharded(inp, mesh, prefer_kernel=False)
    chosen_un, _ = solve_jit(inp)
    assert np.array_equal(np.asarray(chosen_sh), np.asarray(chosen_un))
    batch = decisions_to_names(snap, np.asarray(chosen_sh))
    assert batch == solve_serial(nodes, existing, pending, services)

    report = shard_memory_report(inp, mesh)
    assert report["node_shards"] == 8
    assert report["sharded_bytes_per_device"] > 0
    assert report["total_bytes_per_device"] < (1 << 30)  # sane for HBM


# --------------------------------------------------------------------------
# MeshExecutor: the daemon's device-resident mesh dispatch
# (solver/mesh_exec.py) — delta-wire onto sharded planes, donation
# safety, and wave-loop-through-mesh parity.
# --------------------------------------------------------------------------

from kubernetes_tpu.models.incremental import IncrementalEncoder  # noqa: E402
from kubernetes_tpu.solver.client import RemoteSolver  # noqa: E402
from kubernetes_tpu.solver.service import SolverService  # noqa: E402


def _churn_stream(tag, waves=5, n_nodes=13, wave_pods=6):
    """An IncrementalEncoder churning: each wave's resident planes differ
    from the previous wave's by O(changed) rows (binds accumulate) while
    shapes stay in one pow-2 bucket — the steady state whose device twin
    is the MeshExecutor's resident-plane scatter path."""
    enc = IncrementalEncoder()
    nodes, _, _, services = _cluster(n_nodes=n_nodes, n_pods=0)
    existing = []
    for w in range(waves):
        pending = [api.Pod(
            metadata=api.ObjectMeta(name=f"{tag}-w{w}p{j}",
                                    namespace="default",
                                    uid=f"u-{tag}-{w}-{j}",
                                    labels={"app": "web"} if j % 2 else {}),
            spec=api.PodSpec(containers=[api.Container(
                name="c", image="i",
                resources=api.ResourceRequirements(limits={
                    "cpu": Quantity("200m"),
                    "memory": Quantity("128Mi")}))]))
            for j in range(wave_pods)]
        snap = enc.encode(nodes, existing, pending, services)
        yield snap
        chosen, _ = solve(snap)
        for p, h in zip(pending, decisions_to_names(snap, chosen)):
            if h:
                p.status.host = h
                existing.append(p)


class TestMeshExecutorService:
    """kube-solverd with the mesh dispatch ON (node floor lowered to 1 so
    toy shapes take the mesh path): the delta wire lands on DEVICE-resident
    sharded planes and every decision stays bit-identical to the full-frame
    and in-process paths."""

    def _service(self, **kw):
        kw.setdefault("gather_window_s", 0.001)
        kw.setdefault("mesh", "on")
        kw.setdefault("mesh_min_nodes", 1)
        kw.setdefault("mesh_dispatch", "shard")
        kw.setdefault("mesh_probe", "off")
        return SolverService(**kw).start()

    def test_delta_onto_sharded_planes_bit_identical(self):
        srv = self._service()
        try:
            me = srv._mesh_exec
            assert me is not None and me.node_shards == 8
            cli_delta = RemoteSolver(srv.address, fallback=False,
                                     timeout_s=120)
            cli_full = RemoteSolver(srv.address, fallback=False,
                                    timeout_s=120, delta=False)
            waves = 0
            for snap in _churn_stream("mx"):
                expected = solve(snap)
                got_d = cli_delta.solve(snap)
                got_f = cli_full.solve(snap)
                for got in (got_d, got_f):
                    assert np.array_equal(got[0], expected[0])
                    assert np.array_equal(got[1], expected[1])
                waves += 1
            # every wave of both clients took the mesh path...
            assert me.mesh_waves == 2 * waves
            # ...and the delta client rode the wire: one full frame,
            # then deltas onto the daemon's resident planes
            assert cli_delta.full_waves == 1
            assert cli_delta.delta_waves == waves - 1
            assert cli_delta.resync_waves == 0
        finally:
            srv.stop()

    def test_mesh_parity_probe_counts_clean(self):
        """probe='all': every mesh wave is re-solved in the single-device
        layout and compared bitwise — the live evidence the churn record
        scrapes. A clean stream must count checks, never divergence."""
        srv = self._service(mesh_probe="all")
        try:
            me = srv._mesh_exec
            cli = RemoteSolver(srv.address, fallback=False, timeout_s=120)
            for snap in _churn_stream("mp", waves=3):
                expected = solve(snap)
                got = cli.solve(snap)
                assert np.array_equal(got[0], expected[0])
            assert me.parity_checks >= 3
            assert me.parity_divergent == 0
        finally:
            srv.stop()


class TestMeshExecutorDirect:
    """MeshExecutor unit contracts: device residency, the on-device delta
    scatter, and donation safety."""

    def _executor(self, **kw):
        from kubernetes_tpu.solver.mesh_exec import MeshExecutor
        kw.setdefault("min_nodes", 1)
        kw.setdefault("dispatch", "shard")
        kw.setdefault("probe", "off")
        return MeshExecutor(**kw)

    def _inp(self, n_nodes=13, n_pods=9, tag="d"):
        nodes, existing, pending, services = _cluster(n_nodes=n_nodes,
                                                      n_pods=n_pods)
        snap = encode_snapshot(nodes, existing, pending, services)
        from kubernetes_tpu.models.batch_solver import (
            snapshot_to_host_inputs,
        )
        return snap, snapshot_to_host_inputs(snap)

    def test_resident_planes_survive_donated_solves(self):
        """Donation safety: the per-wave pod planes are donated to the
        compiled program, the resident node/group/zone planes are NOT —
        after any number of solves the cached device buffers must still
        be live (never aliased into a donated slot) and a re-solve from
        them must be bit-identical."""
        me = self._executor()
        snap, inp = self._inp()
        from kubernetes_tpu.models.policy import BatchPolicy
        pol = snap.policy or BatchPolicy()
        key = ("w", "b0")
        first = me.solve(inp, pol, False, cache_key=key)
        entry = me._resident[key]
        devs = {name: rec[1] for name, rec in entry["planes"].items()}
        assert devs and all(not d.is_deleted() for d in devs.values())
        # same host objects again: zero re-transfer, same device buffers,
        # identical decisions — three solves deep
        for _ in range(2):
            again = me.solve(inp, pol, False, cache_key=key)
            assert np.array_equal(first[0], again[0])
            assert np.array_equal(first[1], again[1])
        entry2 = me._resident[key]
        for name, dev in devs.items():
            assert entry2["planes"][name][1] is dev, \
                f"resident plane {name} was re-established"
            assert not dev.is_deleted(), \
                f"resident plane {name} was deleted by a donated solve"

    def test_device_delta_scatter_bit_identical_to_full_transfer(self):
        """The copy-on-write scatter: a wave whose changed planes arrive
        as (base, rows, vals) triples lands on the resident device buffers
        as an on-device row scatter, and decides exactly like a cold full
        transfer of the same host planes."""
        me = self._executor()
        snap, inp = self._inp()
        from kubernetes_tpu.models.policy import BatchPolicy
        pol = snap.policy or BatchPolicy()
        key = ("w", "b0")
        me.solve(inp, pol, False, cache_key=key)

        # service-style copy-on-write delta: two node rows change
        rows = np.array([1, 5], dtype=np.int64)
        new_cap = np.array(inp.cap, copy=True)
        new_cap[rows] = new_cap[rows] // 2
        vals = np.ascontiguousarray(new_cap[rows])
        inp2 = inp._replace(cap=new_cap)
        delta = {"cap": (inp.cap, rows, vals)}

        before = me._m.reshard_bytes.value()
        via_delta = me.solve(inp2, pol, False, cache_key=key, delta=delta)
        assert me._m.reshard_bytes.value() == before, \
            "delta apply must not re-establish (reshard) resident planes"

        cold = self._executor()
        via_full = cold.solve(inp2, pol, False, cache_key=("w2", "b0"))
        assert np.array_equal(via_delta[0], via_full[0])
        assert np.array_equal(via_delta[1], via_full[1])

    def test_dispatch_single_pins_submesh_even_when_pods_axis_fills_devices(
            self):
        """--mesh-dispatch single must win over the node_shards==1 fast
        path: with pods_axis consuming every device the full mesh still
        has one node shard, but the operator pinned the 1x1 submesh."""
        from kubernetes_tpu.models.policy import BatchPolicy
        me = self._executor(pods_axis=8, dispatch="single")
        snap, inp = self._inp()
        mesh, probed = me._active_mesh(inp, snap.policy or BatchPolicy(),
                                       False)
        assert probed is None
        assert mesh is me.submesh
        assert dict(mesh.shape) == {"pods": 1, "nodes": 1}

    def test_dispatch_calibration_persists_winner(self, tmp_path,
                                                  monkeypatch):
        """dispatch='auto' times both layouts once (the probe doubles as
        a bit-identity check), persists the winner in the warm-start dir,
        and a fresh executor skips the probe by reading it back."""
        monkeypatch.setenv("KTPU_WARM_START", "1")
        monkeypatch.setenv("KTPU_CACHE_DIR", str(tmp_path))
        snap, inp = self._inp()
        from kubernetes_tpu.models.policy import BatchPolicy
        pol = snap.policy or BatchPolicy()
        me = self._executor(dispatch="auto")
        me.solve(inp, pol, False, cache_key=("w", "b0"))
        assert me.parity_checks == 1 and me.parity_divergent == 0
        assert len(me._cal) == 1
        # the probed wave still installed device residency: the next wave
        # rides the identity chain instead of a full re-transfer
        planes = me._resident[("w", "b0")]["planes"]
        assert planes and all(not rec[1].is_deleted()
                              for rec in planes.values())
        cal = next(iter(me._cal.values()))
        assert cal["winner"] in ("shard", "single")

        me2 = self._executor(dispatch="auto")
        assert me2._cal == me._cal  # loaded, not re-probed
        me2.solve(inp, pol, False, cache_key=("w", "b0"))
        assert me2.parity_checks == 0  # calibration hit: no probe


def test_wave_loop_through_mesh_parity(monkeypatch):
    """--mesh on against --mesh off: the wave loop whose waves solve
    through the sharded program (node planes resident on the mesh,
    patched between waves) must commit EXACTLY the placements of the
    single-device run. The node floor is lowered and the kernel's domain
    emptied so the toy backlog takes the mesh path for real (counted)."""
    import kubernetes_tpu.parallel.mesh as pm
    from kubernetes_tpu.apiserver.master import Master
    from kubernetes_tpu.client.client import Client, InProcessTransport
    from kubernetes_tpu.scheduler.driver import ConfigFactory
    from kubernetes_tpu.scheduler.tpu_batch import BatchScheduler

    monkeypatch.setattr(pm, "DEFAULT_MESH_MIN_NODES", 1)
    # a wave inside the Pallas kernel's domain takes the one-device arm
    # whatever the mesh (batch_solver._takes_mesh): put these outside it
    from kubernetes_tpu.ops import pallas_solver
    monkeypatch.setattr(pallas_solver, "eligible", lambda *a, **kw: False)

    def run_stack(mesh, n_nodes=10, n_pods=192, wave=64):
        m = Master()
        client = Client(InProcessTransport(m))
        for i in range(n_nodes):
            client.nodes().create(api.Node(
                metadata=api.ObjectMeta(name=f"n{i:03d}"),
                spec=api.NodeSpec(capacity={
                    "cpu": Quantity("64"), "memory": Quantity("256Gi")})))
        for i in range(n_pods):
            client.pods().create(api.Pod(
                metadata=api.ObjectMeta(name=f"p{i:05d}",
                                        namespace="default",
                                        uid=f"uid-{i:05d}"),
                spec=api.PodSpec(containers=[api.Container(
                    name="c", image="img",
                    resources=api.ResourceRequirements(limits={
                        "cpu": Quantity(f"{100 + (i % 8) * 100}m"),
                        "memory": Quantity(f"{128 + (i % 4) * 64}Mi")}))])))
        factory = ConfigFactory(client)
        config = factory.create(mesh=mesh)
        import time as _time
        deadline = _time.monotonic() + 30.0
        while _time.monotonic() < deadline:
            if len(factory.pod_queue.list()) >= n_pods and \
                    len(factory.node_store.list()) >= n_nodes:
                break
            _time.sleep(0.02)
        else:
            pytest.fail("reflectors never synced the backlog")
        sched = BatchScheduler(config, factory, client, wave_size=wave,
                               wave_linger_s=0.02)
        assert (sched._mesh is not None) == (mesh == "on")
        sched.run()
        try:
            deadline = _time.monotonic() + 60.0
            while _time.monotonic() < deadline:
                bound = sum(1 for p in client.pods().list().items
                            if p.spec.host)
                if bound >= n_pods:
                    break
                _time.sleep(0.05)
            placements = {p.metadata.name: p.spec.host
                          for p in client.pods().list().items}
            assert all(placements.values()), "pods never bound"
            return placements
        finally:
            sched.stop()
            factory.stop()

    from kubernetes_tpu.models.batch_solver import wave_programs

    def sharded_waves():
        return sum(v for (program, _platform), v
                   in wave_programs().by_label().items()
                   if program == "scan-sharded")

    before = sharded_waves()
    on = run_stack(mesh="on")
    assert sharded_waves() - before >= 3     # 192 pods in waves of 64
    assert on == run_stack(mesh="off")
