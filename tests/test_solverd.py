"""kube-solverd: protocol, daemon lifecycle, wave coalescing, backpressure,
client fallback — and bit-identity with the in-process solve path.

The contract under test (docs/design/solver.md): a scheduler worker
pointed at the daemon must produce EXACTLY the decisions it would have
produced solving in-process, whether its wave rode alone, was coalesced
into a padded batch with other workers' waves, got a BUSY reply, or the
daemon was down entirely.
"""

import socket
import threading
import time

import numpy as np
import pytest

from kubernetes_tpu.api import types as api
from kubernetes_tpu.api.quantity import Quantity
from kubernetes_tpu.models import gang as gang_mod
from kubernetes_tpu.models.batch_solver import solve
from kubernetes_tpu.models.incremental import IncrementalEncoder
from kubernetes_tpu.models.policy import BatchPolicy, batch_policy_from
from kubernetes_tpu.models.snapshot import encode_snapshot
from kubernetes_tpu.solver import protocol
from kubernetes_tpu.solver.client import (
    RemoteSolver,
    SolverBusy,
    SolverUnavailable,
)
from kubernetes_tpu.solver.service import SolverService


def mk_node(name, cpu="8", mem="16Gi", labels=None):
    return api.Node(
        metadata=api.ObjectMeta(name=name, labels=labels or {}),
        spec=api.NodeSpec(capacity={"cpu": Quantity(cpu),
                                    "memory": Quantity(mem)}))


def mk_pod(name, app="web", cpu="500m", port=0, group=None, gsize=0):
    ann = {}
    if group:
        ann[gang_mod.GANG_NAME_ANNOTATION] = group
        ann[gang_mod.GANG_MIN_MEMBERS_ANNOTATION] = str(gsize)
    ports = [api.ContainerPort(container_port=80, host_port=port)] \
        if port else []
    return api.Pod(
        metadata=api.ObjectMeta(name=name, namespace="default",
                                uid=f"uid-{name}", labels={"app": app},
                                annotations=ann),
        spec=api.PodSpec(containers=[api.Container(
            name="c", image="i", ports=ports,
            resources=api.ResourceRequirements(limits={
                "cpu": Quantity(cpu), "memory": Quantity("512Mi")}))]))


SERVICES = [api.Service(
    metadata=api.ObjectMeta(name="web", namespace="default"),
    spec=api.ServiceSpec(port=80, selector={"app": "web"}))]


def small_snapshot(tag="x", n_nodes=5, n_pods=9):
    nodes = [mk_node(f"{tag}-n{i}") for i in range(n_nodes)]
    pending = [mk_pod(f"{tag}-p{j}", port=7000 + j if j % 3 == 0 else 0)
               for j in range(n_pods)]
    return encode_snapshot(nodes, [], pending, SERVICES)


# -- protocol ----------------------------------------------------------------

class TestProtocol:
    def test_frame_roundtrip_with_arrays(self):
        a, b = socket.socketpair()
        try:
            arrays = (np.arange(12, dtype=np.int32).reshape(3, 4),
                      np.array([True, False, True]),
                      np.zeros((0, 7), np.uint32))
            protocol.send_msg(a, {"op": "solve", "v": 1}, arrays)
            header, got = protocol.recv_msg(b)
            assert header["op"] == "solve"
            assert len(got) == 3
            for x, y in zip(arrays, got):
                assert x.dtype == y.dtype and x.shape == y.shape
                assert np.array_equal(x, y)
            assert got[0].flags.writeable  # independent of the frame buffer
        finally:
            a.close()
            b.close()

    def test_policy_wire_roundtrip(self):
        pol = BatchPolicy(
            use_disk=False,
            label_presence=((("region",), True), (("gpu", "tpu"), False)),
            affinity_labels=("rack",),
            w_lr=2, w_spread=0, w_equal=1,
            label_prefs=(("ssd", True, 3),),
            anti_affinity=(("zone", 2),))
        wire = protocol.policy_to_wire(pol)
        back = protocol.policy_from_wire(wire)
        assert back == pol
        assert hash(back) == hash(pol)  # stays jit-static on the daemon

    def test_fingerprint_binds_policy_and_gangs(self):
        p1, p2 = BatchPolicy(), BatchPolicy(w_lr=2)
        assert protocol.solver_fingerprint(p1, False) == \
            protocol.solver_fingerprint(BatchPolicy(), False)
        assert protocol.solver_fingerprint(p1, False) != \
            protocol.solver_fingerprint(p2, False)
        assert protocol.solver_fingerprint(p1, False) != \
            protocol.solver_fingerprint(p1, True)

    def test_eof_returns_none(self):
        a, b = socket.socketpair()
        a.close()
        try:
            assert protocol.recv_msg(b) is None
        finally:
            b.close()


# -- daemon lifecycle --------------------------------------------------------

class TestDaemonLifecycle:
    def test_start_ping_stop(self):
        srv = SolverService().start()
        addr = srv.address
        try:
            cli = RemoteSolver(addr)
            pong = cli.ping()
            assert pong["v"] == protocol.PROTOCOL_VERSION
            assert pong["solves"] == 0
        finally:
            srv.stop()
        # a stopped daemon refuses new work; the client surfaces it
        cli2 = RemoteSolver(addr, connect_timeout_s=0.3,
                            fallback=False)
        with pytest.raises(SolverUnavailable):
            cli2.ping()

    def test_version_skew_rejected(self):
        srv = SolverService().start()
        try:
            sock = socket.create_connection(
                ("127.0.0.1", srv.port), timeout=2)
            snap = small_snapshot("skew", 3, 2)
            from kubernetes_tpu.models.batch_solver import (
                snapshot_to_host_inputs)
            host = snapshot_to_host_inputs(snap)
            protocol.send_msg(sock, {
                "op": "solve", "v": 999,
                "policy": protocol.policy_to_wire(BatchPolicy()),
                "gangs": False}, tuple(host))
            header, _ = protocol.recv_msg(sock)
            assert "err" in header and "version skew" in header["msg"]
            sock.close()
        finally:
            srv.stop()


# -- solve correctness -------------------------------------------------------

class TestRemoteSolve:
    def test_bit_identical_to_in_process(self):
        snap = small_snapshot("solo", 6, 11)
        expected_chosen, expected_scores = solve(snap)
        srv = SolverService(gather_window_s=0.005).start()
        try:
            cli = RemoteSolver(srv.address, fallback=False, timeout_s=120)
            chosen, scores = cli.solve(snap)
            assert np.array_equal(chosen, expected_chosen)
            assert np.array_equal(scores, expected_scores)
            assert cli.remote_waves == 1 and srv.solve_calls == 1
        finally:
            srv.stop()

    def test_gang_wave_bit_identical(self):
        # 3 gangs x 3 pods on 4 small nodes: some gangs must roll back,
        # exercising the checkpointed scan + client-side post-pass
        nodes = [mk_node(f"gg{i}", cpu="2") for i in range(4)]
        pending = [mk_pod(f"gp{g}-{m}", cpu="900m", group=f"grp{g}", gsize=3)
                   for g in range(3) for m in range(3)]
        snap = encode_snapshot(nodes, [], pending, SERVICES)
        assert snap.has_gangs
        expected = solve(snap)
        srv = SolverService(gather_window_s=0.005).start()
        try:
            cli = RemoteSolver(srv.address, fallback=False, timeout_s=120)
            got = cli.solve(snap)
            assert np.array_equal(got[0], expected[0])
            assert np.array_equal(got[1], expected[1])
        finally:
            srv.stop()


# -- wave coalescing ---------------------------------------------------------

class TestCoalescing:
    def test_concurrent_waves_coalesce_and_stay_bit_identical(self):
        """K concurrent requesters with HETEROGENEOUS shapes (node counts,
        pod counts, full vs incremental encoder) must resolve in fewer
        than K device calls, each bit-identical to its own in-process
        solve — the padding-invariance contract."""
        shapes = [(5, 9, False), (7, 13, True), (3, 4, False),
                  (11, 20, True), (5, 9, False), (6, 17, True)]
        snaps = []
        for k, (nn, pp, incremental) in enumerate(shapes):
            nodes = [mk_node(f"c{k}-n{i}") for i in range(nn)]
            pending = [mk_pod(f"c{k}-p{j}",
                              port=7100 + j if j % 3 == 0 else 0)
                       for j in range(pp)]
            if incremental:
                snaps.append(IncrementalEncoder().encode(
                    nodes, [], pending, SERVICES))
            else:
                snaps.append(encode_snapshot(nodes, [], pending, SERVICES))
        expected = [solve(s) for s in snaps]

        srv = SolverService(gather_window_s=0.5, max_batch=16).start()
        try:
            results = [None] * len(snaps)
            errors = []

            def worker(i):
                try:
                    cli = RemoteSolver(srv.address, fallback=False,
                                       timeout_s=180)
                    results[i] = cli.solve(snaps[i])
                except Exception as e:  # noqa: BLE001
                    errors.append((i, e))

            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(len(snaps))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300)
            assert not errors, errors
            assert srv.waves_served == len(snaps)
            assert srv.solve_calls < len(snaps), (
                f"{srv.solve_calls} device calls for {len(snaps)} waves: "
                "no coalescing happened")
            for i, (got, want) in enumerate(zip(results, expected)):
                assert np.array_equal(got[0], want[0]), i
                assert np.array_equal(got[1], want[1]), i
        finally:
            srv.stop()

    def test_zone_anti_affinity_waves_coalesce_across_zone_vocabs(self):
        """Two waves under the same anti-affinity policy but different
        zone-value vocabularies (V axis) coalesce into one call and stay
        exact — the zone-onehot zero-padding invariant."""
        from kubernetes_tpu.scheduler.plugins import (
            Policy, PolicyPredicate, PolicyPriority)
        pol = Policy(
            predicates=[PolicyPredicate(name=n) for n in
                        ("PodFitsPorts", "PodFitsResources",
                         "NoDiskConflict", "MatchNodeSelector", "HostName")],
            priorities=[
                PolicyPriority(name="LeastRequestedPriority", weight=1),
                PolicyPriority(name="zoneSpread", weight=2,
                               service_anti_affinity_label="zone")])
        bp = batch_policy_from(policy=pol)
        n1 = [mk_node(f"za-{i}", labels={"zone": f"z{i % 2}"})
              for i in range(6)]
        n2 = [mk_node(f"zb-{i}", labels={"zone": f"z{i % 5}"})
              for i in range(9)]
        s1 = encode_snapshot(n1, [], [mk_pod(f"zap{j}") for j in range(7)],
                             SERVICES, policy=bp)
        s2 = encode_snapshot(n2, [], [mk_pod(f"zbp{j}") for j in range(11)],
                             SERVICES, policy=bp)
        expected = [solve(s1), solve(s2)]

        srv = SolverService(gather_window_s=0.5, max_batch=8).start()
        try:
            results = [None, None]

            def worker(i, snap):
                cli = RemoteSolver(srv.address, fallback=False,
                                   timeout_s=180)
                results[i] = cli.solve(snap)

            threads = [threading.Thread(target=worker, args=(i, s))
                       for i, s in enumerate((s1, s2))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300)
            assert srv.solve_calls == 1, "zone waves did not coalesce"
            for i in range(2):
                assert np.array_equal(results[i][0], expected[i][0]), i
                assert np.array_equal(results[i][1], expected[i][1]), i
        finally:
            srv.stop()


# -- backpressure ------------------------------------------------------------

class TestBackpressure:
    def test_busy_when_queue_full_and_fallback_recovers(self):
        snap = small_snapshot("busy", 4, 3)
        expected = solve(snap)
        srv = SolverService(gather_window_s=0.001, max_batch=1, max_queue=1)
        entered = threading.Event()
        release = threading.Event()
        real_solve = srv._device_solve

        def slow_solve(stacked, pol, gangs):
            entered.set()
            assert release.wait(timeout=60)
            return real_solve(stacked, pol, gangs)

        srv._device_solve = slow_solve
        srv.start()
        try:
            results = {}

            def req(name):
                cli = RemoteSolver(srv.address, fallback=False,
                                   timeout_s=120)
                results[name] = cli.solve(snap)

            t1 = threading.Thread(target=req, args=("first",))
            t1.start()
            assert entered.wait(timeout=60)   # solver thread is busy now
            t2 = threading.Thread(target=req, args=("second",))
            t2.start()
            deadline = time.monotonic() + 10
            while len(srv._pending) < 1 and time.monotonic() < deadline:
                time.sleep(0.01)              # second wave queued
            assert len(srv._pending) == 1

            # the queue is full: a third wave bounces with BUSY...
            strict = RemoteSolver(srv.address, fallback=False, timeout_s=30)
            with pytest.raises(SolverBusy):
                strict.solve(snap)
            # ...and a fallback client solves in-process, bit-identically,
            # WITHOUT entering the unhealthy cooldown (busy != dead)
            soft = RemoteSolver(srv.address, timeout_s=30)
            got = soft.solve(snap)
            assert np.array_equal(got[0], expected[0])
            assert soft.busy_waves == 1 and not soft._in_cooldown()

            release.set()
            t1.join(timeout=120)
            t2.join(timeout=120)
            assert np.array_equal(results["first"][0], expected[0])
            assert np.array_equal(results["second"][0], expected[0])
        finally:
            release.set()
            srv.stop()


# -- client fallback ---------------------------------------------------------

class TestFallback:
    def test_daemon_absent_falls_back_and_cools_down(self):
        snap = small_snapshot("dead", 4, 5)
        expected = solve(snap)
        cli = RemoteSolver("127.0.0.1:1", connect_timeout_s=0.2,
                           cooldown_s=30.0)
        t0 = time.monotonic()
        got = cli.solve(snap)
        first_s = time.monotonic() - t0
        assert np.array_equal(got[0], expected[0])
        assert cli.fallback_waves == 1 and cli._in_cooldown()
        # inside the cooldown the next wave pays ZERO connect attempts
        t0 = time.monotonic()
        got2 = cli.solve(snap)
        assert np.array_equal(got2[0], expected[0])
        assert time.monotonic() - t0 < first_s + 0.5
        assert cli.fallback_waves == 2

    def test_no_fallback_raises(self):
        snap = small_snapshot("strict", 3, 2)
        cli = RemoteSolver("127.0.0.1:1", connect_timeout_s=0.2,
                           fallback=False)
        with pytest.raises(SolverUnavailable):
            cli.solve(snap)

    def test_daemon_restart_retries_stale_pooled_connection(self):
        """A daemon restart half-closes the client's pooled socket: the
        next send 'succeeds' into the dead socket and the recv fails. The
        failure rode a REUSED connection, so the client must retry once on
        a fresh one and reach the restarted daemon — not mark it
        unhealthy."""
        snap = small_snapshot("restart", 4, 5)
        expected = solve(snap)
        srv1 = SolverService(gather_window_s=0.005).start()
        port = srv1.port
        cli = RemoteSolver(srv1.address, fallback=False, timeout_s=120)
        got = cli.solve(snap)
        assert np.array_equal(got[0], expected[0])
        srv1.stop()
        srv2 = None
        deadline = time.monotonic() + 10
        while srv2 is None:
            try:
                srv2 = SolverService(port=port, gather_window_s=0.005)
            except OSError:   # old socket still tearing down
                assert time.monotonic() < deadline, "port never freed"
                time.sleep(0.1)
        srv2.start()
        try:
            got2 = cli.solve(snap)   # pooled socket is stale; must recover
            assert np.array_equal(got2[0], expected[0])
            assert cli.remote_waves == 2 and not cli._in_cooldown()
        finally:
            srv2.stop()


# -- the delta wire (protocol v2) --------------------------------------------

class TestDeltaWire:
    """Bit-identity contract of the delta wire: a wave solved via plane
    deltas against the daemon's resident cache must decide EXACTLY like
    the same wave shipped as a full frame and like the in-process solve —
    across churn, injected epoch skew, and a daemon restart mid-stream."""

    @staticmethod
    def _churn_stream(tag, waves=5, n_nodes=6, wave_pods=5):
        """One incremental encoder churning: each yielded snapshot's
        resident planes differ from the previous wave's by O(changed)
        rows (binds accumulate), while shapes stay in one pow-2 bucket —
        the steady state the delta wire exists for."""
        from kubernetes_tpu.models.batch_solver import decisions_to_names

        enc = IncrementalEncoder()
        nodes = [mk_node(f"{tag}-n{i}") for i in range(n_nodes)]
        existing = []
        for w in range(waves):
            pending = [mk_pod(f"{tag}-w{w}p{j}") for j in range(wave_pods)]
            snap = enc.encode(nodes, existing, pending, SERVICES)
            yield snap
            chosen, _ = solve(snap)
            for p, h in zip(pending, decisions_to_names(snap, chosen)):
                if h:
                    p.status.host = h
                    existing.append(p)

    def test_delta_stream_bit_identical_to_full_and_in_process(self):
        srv = SolverService(gather_window_s=0.001).start()
        try:
            cli_delta = RemoteSolver(srv.address, fallback=False,
                                     timeout_s=120)
            cli_full = RemoteSolver(srv.address, fallback=False,
                                    timeout_s=120, delta=False)
            for snap in self._churn_stream("dw"):
                expected = solve(snap)
                got_d = cli_delta.solve(snap)
                got_f = cli_full.solve(snap)
                for got in (got_d, got_f):
                    assert np.array_equal(got[0], expected[0])
                    assert np.array_equal(got[1], expected[1])
            # the stream stayed in one shape bucket: wave 1 established
            # the cache, every later wave rode deltas and shipped less
            assert cli_delta.full_waves == 1
            assert cli_delta.delta_waves == 4
            assert cli_delta.resync_waves == 0
            assert srv.delta_waves == 4
            assert cli_delta.delta_bytes_shipped < cli_delta.delta_bytes_full
            # the full-frame client never touched the delta path
            assert cli_full.delta_waves == 0 and cli_full.full_waves == 0
        finally:
            srv.stop()

    def test_epoch_skew_resyncs_and_recovers(self):
        srv = SolverService(gather_window_s=0.001).start()
        try:
            cli = RemoteSolver(srv.address, fallback=False, timeout_s=120)
            snaps = list(self._churn_stream("ep"))
            expected = [solve(s) for s in snaps]
            got = cli.solve(snaps[0])
            assert np.array_equal(got[0], expected[0][0])
            # desync the pair: pretend the client applied frames the
            # daemon never saw (a lost reply's worst case)
            for mir in cli._local.mirrors.values():
                mir.epoch += 3
            got = cli.solve(snaps[1])
            assert np.array_equal(got[0], expected[1][0])
            assert cli.resync_waves == 1
            assert srv.resync_replies == 1
            # the full-frame resend re-established the pair: back to deltas
            got = cli.solve(snaps[2])
            assert np.array_equal(got[0], expected[2][0])
            assert cli.delta_waves == 1
        finally:
            srv.stop()

    def test_daemon_restart_mid_stream_resyncs_no_cache(self):
        snaps = list(self._churn_stream("rs"))
        expected = [solve(s) for s in snaps]
        srv1 = SolverService(gather_window_s=0.001).start()
        port = srv1.port
        cli = RemoteSolver(srv1.address, fallback=False, timeout_s=120)
        for i in (0, 1):
            got = cli.solve(snaps[i])
            assert np.array_equal(got[0], expected[i][0])
        assert cli.delta_waves == 1
        srv1.stop()
        srv2 = None
        deadline = time.monotonic() + 10
        while srv2 is None:
            try:
                srv2 = SolverService(port=port, gather_window_s=0.001)
            except OSError:
                assert time.monotonic() < deadline, "port never freed"
                time.sleep(0.1)
        srv2.start()
        try:
            # the restarted daemon has no cache: the delta attempt must
            # resync to a full frame (after the stale-socket retry), and
            # later waves ride deltas against the fresh entry
            got = cli.solve(snaps[2])
            assert np.array_equal(got[0], expected[2][0])
            assert cli.resync_waves == 1
            got = cli.solve(snaps[3])
            assert np.array_equal(got[0], expected[3][0])
            assert cli.delta_waves == 2
        finally:
            srv2.stop()

    def test_v1_full_frame_client_still_served(self):
        """Version negotiation: a v1 client (no cache/planes, fingerprint
        derived with v=1) against the v2 daemon gets full-plane service,
        not an error."""
        from kubernetes_tpu.models.batch_solver import (
            snapshot_to_host_inputs)

        snap = small_snapshot("v1c", 4, 6)
        expected = solve(snap)
        srv = SolverService(gather_window_s=0.001).start()
        try:
            sock = socket.create_connection(("127.0.0.1", srv.port),
                                            timeout=10)
            sock.settimeout(120)
            host = snapshot_to_host_inputs(snap)
            protocol.send_msg(sock, {
                "op": "solve", "v": 1,
                "fp": protocol.solver_fingerprint(BatchPolicy(), False,
                                                  version=1),
                "policy": protocol.policy_to_wire(BatchPolicy()),
                "gangs": False}, tuple(host))
            header, arrays = protocol.recv_msg(sock)
            assert header.get("ok"), header
            assert np.array_equal(arrays[0], expected[0])
            sock.close()
        finally:
            srv.stop()

    def test_shape_bucket_tracks_layout(self):
        a = (np.zeros((4, 2), np.int32), np.ones(3, bool))
        same = (np.ones((4, 2), np.int32) * 7, np.zeros(3, bool))
        grown = (np.zeros((8, 2), np.int32), np.ones(3, bool))
        widened = (np.zeros((4, 2), np.int64), np.ones(3, bool))
        assert protocol.shape_bucket(a) == protocol.shape_bucket(same)
        assert protocol.shape_bucket(a) != protocol.shape_bucket(grown)
        assert protocol.shape_bucket(a) != protocol.shape_bucket(widened)


# -- the scheduler end-to-end ------------------------------------------------

class TestSchedulerIntegration:
    def test_batch_scheduler_through_solverd(self):
        """The test_tpu_batch spread scenario, waves solved by the daemon:
        12 service pods over 4 nodes must spread 3/3/3/3, and the waves
        must actually have gone remote."""
        from kubernetes_tpu.apiserver.master import Master
        from kubernetes_tpu.client.client import Client, InProcessTransport
        from kubernetes_tpu.scheduler.driver import ConfigFactory
        from kubernetes_tpu.scheduler.tpu_batch import BatchScheduler

        srv = SolverService(gather_window_s=0.005).start()
        m = Master()
        client = Client(InProcessTransport(m))
        for i in range(4):
            client.nodes().create(mk_node(f"n{i}"))
        client.services().create(SERVICES[0])
        factory = ConfigFactory(client)
        config = factory.create(solver_addr=srv.address)
        assert config.solver_addr == srv.address
        sched = BatchScheduler(config, factory, client, wave_size=64,
                               wave_linger_s=0.1)
        assert sched.solver is not None
        sched.run()
        try:
            time.sleep(0.3)  # reflectors sync
            for i in range(12):
                client.pods().create(mk_pod(f"w{i}"))
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                pods = client.pods().list().items
                if pods and all(p.spec.host for p in pods):
                    break
                time.sleep(0.05)
            placement = {}
            for p in client.pods().list().items:
                assert p.spec.host, "wave stalled against solverd"
                placement[p.spec.host] = placement.get(p.spec.host, 0) + 1
            assert sorted(placement.values()) == [3, 3, 3, 3], placement
            assert sched.solver.remote_waves >= 1
            assert srv.waves_served >= sched.solver.remote_waves
        finally:
            sched.stop()
            factory.stop()
            srv.stop()
