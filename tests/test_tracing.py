"""kube-trace (util/tracing.py): span nesting and ordering, the ring
buffer's never-block/evict-oldest contract, trace-context propagation
over the delta wire (v3) and over HTTP (X-KTPU-Trace, live two-process),
Chrome-trace export validity, the <1% disabled-path overhead guard, and
the Histogram.quantile semantics the latency record section relies on;
then ``tracing.phase`` — one instrumentation point, three sinks (always-on
histogram + off-CPU counter, a ``ktpu/`` annotation on the profiler's
clock, the kube-trace span) — the wave loop's sites that go through it,
the FIFO's queue wait and the thread-role CPU counters.

The contract under test (docs/design/observability.md): tracing OFF is
free and the default; tracing ON never blocks a hot path (the ring
evicts, counts the loss, and keeps going); span context crosses every
process boundary the stack has so the merged per-run artifact shows one
pod-wave's causal path end to end.
"""

import json
import os
import socket
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from kubernetes_tpu.api import types as api
from kubernetes_tpu.api.quantity import Quantity
from kubernetes_tpu.models.batch_solver import solve
from kubernetes_tpu.models.snapshot import encode_snapshot
from kubernetes_tpu.solver import protocol
from kubernetes_tpu.solver.client import RemoteSolver
from kubernetes_tpu.solver.service import SolverService
from kubernetes_tpu.util import metrics, tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _tracing_off_after():
    """Every test leaves the process the way production starts: tracing
    disabled, ring drained (tracing state is process-global)."""
    yield
    tracing.drain()
    tracing.disable()


def fresh(capacity=4096):
    tracing.enable("test", capacity=capacity)
    tracing.drain()


def mk_node(name):
    return api.Node(
        metadata=api.ObjectMeta(name=name),
        spec=api.NodeSpec(capacity={"cpu": Quantity("8"),
                                    "memory": Quantity("16Gi")}))


def mk_pod(name):
    return api.Pod(
        metadata=api.ObjectMeta(name=name, namespace="default",
                                uid=f"uid-{name}", labels={"app": "web"}),
        spec=api.PodSpec(containers=[api.Container(
            name="c", image="i",
            resources=api.ResourceRequirements(limits={
                "cpu": Quantity("500m"), "memory": Quantity("512Mi")}))]))


def small_snapshot(tag="tr", n_nodes=5, n_pods=9):
    nodes = [mk_node(f"{tag}-n{i}") for i in range(n_nodes)]
    pending = [mk_pod(f"{tag}-p{j}") for j in range(n_pods)]
    return encode_snapshot(nodes, [], pending, [])


# -- spans -------------------------------------------------------------------

class TestSpans:
    def test_nesting_and_ordering(self):
        fresh()
        with tracing.span("outer", parent=None, wave=7) as outer:
            with tracing.span("inner") as inner:
                time.sleep(0.001)
        assert inner.ctx[0] == outer.ctx[0]  # one trace
        spans = tracing.drain()["spans"]
        assert [s["name"] for s in spans] == ["inner", "outer"]
        i, o = spans
        assert i["tid"] == o["tid"]
        assert i["psid"] == o["sid"]       # nesting via ambient context
        assert o["psid"] == ""             # root
        assert o["attrs"] == {"wave": 7}
        # containment on the one monotonic axis
        assert i["t0"] >= o["t0"]
        assert i["t0"] + i["dur"] <= o["t0"] + o["dur"]

    def test_disabled_is_nop_and_records_nothing(self):
        fresh()
        tracing.disable()
        s = tracing.span("x")
        assert s is tracing.NOP
        with s:
            assert tracing.current() is None
        tracing.record("y", 0, 10)
        assert tracing.new_ctx() is None
        assert tracing.wire() == ""
        tracing.enable("test")
        assert tracing.drain()["spans"] == []

    def test_child_span_outside_any_trace_is_nop(self):
        """Shared internals (registry writes) traced only under a traced
        request: 50k untraced feeder creates must not churn the ring."""
        fresh()
        assert tracing.child_span("store.create") is tracing.NOP
        with tracing.span("req"):
            with tracing.child_span("store.create") as c:
                assert c is not tracing.NOP
        names = [s["name"] for s in tracing.drain()["spans"]]
        assert names == ["store.create", "req"]

    def test_exception_tags_span_and_propagates(self):
        fresh()
        with pytest.raises(ValueError):
            with tracing.span("boom"):
                raise ValueError("x")
        (sp,) = tracing.drain()["spans"]
        assert sp["attrs"]["error"] == "ValueError"

    def test_explicit_parent_crosses_threads(self):
        fresh()
        ctx = tracing.new_ctx()
        done = threading.Event()

        def worker():
            with tracing.span("stage", parent=ctx):
                pass
            done.set()

        threading.Thread(target=worker).start()
        assert done.wait(5)
        (sp,) = tracing.drain()["spans"]
        assert sp["tid"] == ctx[0] and sp["psid"] == ctx[1]

    def test_record_retroactive_span(self):
        fresh()
        ctx = tracing.new_ctx()
        tracing.record("wave.drain", 100, 250, parent=ctx, pods=4)
        (sp,) = tracing.drain()["spans"]
        assert (sp["tid"], sp["psid"]) == ctx
        assert sp["t0"] == 100 and sp["dur"] == 150


# -- ring buffer -------------------------------------------------------------

class TestRing:
    def test_bounded_eviction_counts_dropped_never_blocks(self):
        fresh(capacity=64)
        for i in range(200):
            tracing.record("s", i, i + 1, idx=i)
        shard = tracing.drain()
        assert len(shard["spans"]) == 64          # bounded
        assert shard["dropped"] == 200 - 64       # loss counted, not hidden
        assert shard["written"] == 200
        # the survivors are the NEWEST spans, in write order
        kept = [s["attrs"]["idx"] for s in shard["spans"]]
        assert kept == list(range(136, 200))

    def test_drain_before_enable_is_empty_not_an_error(self):
        """A /debug/trace hit on a process that never enabled tracing
        (the default) must answer an empty shard — the ring is allocated
        lazily by enable(), so the disabled path is allocation-free."""
        saved = tracing._state.ring
        try:
            tracing.disable()
            tracing._state.ring = None
            shard = tracing.drain()
            assert shard["spans"] == []
            assert shard["written"] == 0 and shard["dropped"] == 0
        finally:
            tracing._state.ring = saved

    def test_drain_returns_each_span_once(self):
        fresh(capacity=64)
        tracing.record("a", 0, 1)
        assert len(tracing.drain()["spans"]) == 1
        assert tracing.drain()["spans"] == []
        tracing.record("b", 1, 2)
        shard = tracing.drain()
        assert [s["name"] for s in shard["spans"]] == ["b"]
        assert shard["dropped"] == 0

    def test_peek_drain_preserves_cursor(self):
        fresh(capacity=64)
        tracing.record("a", 0, 1)
        assert len(tracing.drain(reset=False)["spans"]) == 1
        assert len(tracing.drain()["spans"]) == 1  # still there

    def test_concurrent_writers_never_error(self):
        fresh(capacity=128)
        stop = threading.Event()
        errs = []

        def writer():
            try:
                while not stop.is_set():
                    with tracing.span("w"):
                        pass
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=writer) for _ in range(4)]
        for t in threads:
            t.start()
        for _ in range(20):
            tracing.drain()
            time.sleep(0.001)
        stop.set()
        for t in threads:
            t.join(5)
        assert not errs


# -- wire form ---------------------------------------------------------------

class TestWireForm:
    def test_wire_parse_roundtrip(self):
        fresh()
        with tracing.span("x") as sp:
            w = tracing.wire()
        assert w and tracing.parse(w) == sp.ctx

    @pytest.mark.parametrize("junk", [
        None, "", "noseparator", "-", "a-", "-b", 42, b"x-y",
        "t" * 65 + "-s", "t-" + "s" * 65])
    def test_parse_tolerates_junk(self, junk):
        assert tracing.parse(junk) is None

    def test_protocol_parse_trace(self):
        assert protocol.parse_trace({"trace": ["t1", "s1"]}) == ("t1", "s1")
        for bad in ({}, {"trace": None}, {"trace": "t-s"},
                    {"trace": ["t"]}, {"trace": ["t", ""]},
                    {"trace": [1, 2]}, {"trace": ["t" * 65, "s"]}):
            assert protocol.parse_trace(bad) is None


# -- delta wire (v3 daemon) --------------------------------------------------

class TestDeltaWireTrace:
    def test_v3_trace_context_attaches_daemon_spans(self):
        """The wave's ambient span rides the solve frame; the daemon's
        queue/solve spans land on the SAME trace id — and the decisions
        stay bit-identical to in-process."""
        srv = SolverService(gather_window_s=0.005).start()
        try:
            fresh()
            rs = RemoteSolver(srv.address, fallback=False)
            snap = small_snapshot("v3")
            with tracing.span("wave.solve") as sp:
                chosen, scores = rs.solve(snap)
            tid = sp.ctx[0]
            spans = tracing.drain()["spans"]
            names = {s["name"] for s in spans if s["tid"] == tid}
            assert "solverd.queue" in names
            assert "solverd.solve" in names
            c2, s2 = solve(snap)
            assert np.array_equal(chosen, c2)
            assert np.array_equal(scores, s2)
        finally:
            srv.stop()

    def test_traceless_frame_served_untraced(self):
        """No ambient span -> no trace field on the frame -> the daemon
        serves it identically but records no spans for it."""
        srv = SolverService(gather_window_s=0.005).start()
        try:
            fresh()
            rs = RemoteSolver(srv.address, fallback=False)
            snap = small_snapshot("nt")
            chosen, _ = rs.solve(snap)  # outside any span
            spans = tracing.drain()["spans"]
            assert not any(s["name"].startswith("solverd.") for s in spans)
            assert np.array_equal(chosen, solve(snap)[0])
        finally:
            srv.stop()

    def test_v2_client_served_untraced_by_v3_daemon(self, monkeypatch):
        """A v2 client (pre-trace protocol) never sends the field; the
        v3 daemon must serve it exactly as before."""
        srv = SolverService(gather_window_s=0.005).start()
        try:
            fresh()
            orig_fp = protocol.solver_fingerprint
            monkeypatch.setattr(protocol, "PROTOCOL_VERSION", 2)
            # a real v2 client derives its fingerprint with ITS version
            monkeypatch.setattr(
                protocol, "solver_fingerprint",
                lambda pol, gangs, version=2: orig_fp(pol, gangs,
                                                      version=version))
            rs = RemoteSolver(srv.address, fallback=False)
            snap = small_snapshot("v2")
            chosen, _ = rs.solve(snap)
            assert rs.remote_waves == 1  # served remotely, no fallback
            spans = tracing.drain()["spans"]
            assert not any(s["name"].startswith("solverd.") for s in spans)
            assert np.array_equal(chosen, solve(snap)[0])
        finally:
            srv.stop()

    def test_trace_field_never_changes_the_fingerprint(self):
        """Two waves differing only in trace context must coalesce into
        one compiled program family: the fingerprint ignores the trace
        header field by construction."""
        pol_fp = protocol.solver_fingerprint
        from kubernetes_tpu.models.policy import BatchPolicy
        assert pol_fp(BatchPolicy(), False) == pol_fp(BatchPolicy(), False)


# -- HTTP propagation (live two-process) -------------------------------------

class TestHTTPPropagation:
    @pytest.fixture()
    def live_apiserver(self):
        port = 18731
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=REPO + (os.pathsep + os.environ["PYTHONPATH"]
                                      if os.environ.get("PYTHONPATH")
                                      else ""))
        proc = subprocess.Popen(
            [sys.executable, "-m", "kubernetes_tpu.cmd.apiserver",
             "--port", str(port), "--trace"],
            env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        base = f"http://127.0.0.1:{port}"
        try:
            deadline = time.time() + 30
            while time.time() < deadline:
                try:
                    urllib.request.urlopen(f"{base}/healthz", timeout=1)
                    break
                except Exception:
                    if proc.poll() is not None:
                        raise RuntimeError("apiserver child died")
                    time.sleep(0.2)
            else:
                raise RuntimeError("apiserver never became healthy")
            yield base, port
        finally:
            proc.terminate()
            proc.wait(10)

    def test_header_propagates_through_live_bind(self, live_apiserver):
        """Client span -> X-KTPU-Trace header -> the OTHER process's
        handler + store spans carry the same trace id, drained via its
        GET /debug/trace."""
        base, port = live_apiserver
        from kubernetes_tpu.client.client import Client
        from kubernetes_tpu.client.http import HTTPTransport
        fresh()
        client = Client(HTTPTransport(base))
        client.nodes().create(mk_node("trace-n0"))
        with tracing.span("test.bind") as sp:
            client.pods("default").create(mk_pod("trace-p0"))
            client.pods("default").bind(api.Binding(
                metadata=api.ObjectMeta(name="trace-p0",
                                        namespace="default"),
                pod_name="trace-p0", host="trace-n0"))
        tid = sp.ctx[0]
        shard = json.loads(urllib.request.urlopen(
            f"{base}/debug/trace", timeout=10).read())
        assert shard["service"] == "apiserver"
        remote = [s for s in shard["spans"] if s["tid"] == tid]
        names = {s["name"] for s in remote}
        assert "http.post" in names          # handler span joined
        assert "store.create" in names       # registry write leg
        # the server-side spans parent back into the client's trace
        assert all(s["psid"] for s in remote)
        # our own client-side span stayed in OUR ring, not the server's
        assert "test.bind" in {s["name"] for s in tracing.drain()["spans"]}

    def test_untraced_requests_record_nothing_serverside(self,
                                                         live_apiserver):
        base, _port = live_apiserver
        from kubernetes_tpu.client.client import Client
        from kubernetes_tpu.client.http import HTTPTransport
        urllib.request.urlopen(f"{base}/debug/trace", timeout=10)  # clear
        client = Client(HTTPTransport(base))
        client.nodes().create(mk_node("quiet-n0"))  # tracing off here
        shard = json.loads(urllib.request.urlopen(
            f"{base}/debug/trace", timeout=10).read())
        assert shard["spans"] == []

    def test_watch_stream_echoes_trace_header(self, live_apiserver):
        base, port = live_apiserver
        fresh()
        with tracing.span("test.watch") as sp:
            w = tracing.wire()
            s = socket.create_connection(("127.0.0.1", port), timeout=10)
            try:
                s.sendall(
                    b"GET /api/v1/pods?watch=1 HTTP/1.1\r\nHost: a\r\n"
                    + tracing.HEADER.encode() + b": " + w.encode()
                    + b"\r\n\r\n")
                head = b""
                while b"\r\n\r\n" not in head:
                    head += s.recv(4096)
            finally:
                s.close()
        assert f"{tracing.HEADER}: {w}".encode() in head
        assert w == tracing.wire(sp.ctx)


# -- chrome-trace export -----------------------------------------------------

class TestChromeExport:
    def test_merged_export_is_valid_chrome_trace_json(self, tmp_path):
        fresh()
        with tracing.span("wave", pods=2):
            with tracing.span("encode"):
                pass
        shard_a = tracing.drain()
        shard_b = {"service": "solverd", "pid": 999, "written": 1,
                   "dropped": 3, "spans": [
                       {"name": "solverd.solve", "tid": "t2", "sid": "s2",
                        "psid": "p2", "t0": 5_000_000, "dur": 1_000_000,
                        "thr": "solve-0", "attrs": {"coalesced": 2}}]}
        path = tracing.dump_chrome([shard_a, shard_b],
                                   str(tmp_path / "merged_trace.json"))
        with open(path) as fh:
            doc = json.loads(fh.read())     # json.loads-valid export
        events = doc["traceEvents"]
        x = [e for e in events if e["ph"] == "X"]
        meta = [e for e in events if e["ph"] == "M"]
        assert len(x) == 3
        # per-process metadata names both shards
        proc_names = {e["args"]["name"] for e in meta
                      if e["name"] == "process_name"}
        assert {"test", "solverd"} <= proc_names
        for e in x:
            assert e["ts"] >= 0 and e["dur"] >= 0
            assert "trace_id" in e["args"] and "span_id" in e["args"]
        # microseconds: the solverd span's 1ms duration
        sd = next(e for e in x if e["name"] == "solverd.solve")
        assert sd["dur"] == pytest.approx(1000.0)
        assert sd["pid"] == 999


# -- tracing.phase: one helper, three sinks ----------------------------------

def _offcpu(name):
    return tracing._OFFCPU.value(name)


def _spin(seconds):
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


class TestPhase:
    def test_feeds_sum_count_and_offcpu_from_one_reading(self):
        h = metrics.Histogram("h", "t", ("part",), buckets=(0.1, 1.0))
        off0 = _offcpu("t.phase.a")
        for _ in range(3):
            with tracing.phase("t.phase.a", h, "a") as ph:
                time.sleep(0.002)
        assert h.count("a") == 3
        assert h.sum("a") >= 3 * 0.002
        # the block's own reading is what the histogram got last
        assert ph.wall_s >= 0.002 and h.sum("a") >= ph.wall_s
        off = _offcpu("t.phase.a") - off0
        assert 0.0 <= off <= h.sum("a")
        # no histogram, no off-CPU series (annotation-only sites)
        with tracing.phase("t.phase.bare"):
            pass
        assert ("t.phase.bare",) not in tracing._OFFCPU.by_label()

    @pytest.mark.parametrize("body,lo,hi", [
        ("sleep", 0.8, 1.0),     # held open without running: all off-CPU
        ("spin", 0.0, 0.25),     # ran the whole time: next to none
    ])
    def test_offcpu_is_wall_less_thread_cpu(self, body, lo, hi):
        h = metrics.Histogram("h", "t", buckets=(1.0,))
        name = "t.phase." + body
        shares = []
        for _ in range(3):       # best of three: a loaded box preempts a spin
            off0, sum0 = _offcpu(name), h.sum()
            with tracing.phase(name, h):
                time.sleep(0.05) if body == "sleep" else _spin(0.05)
            shares.append((_offcpu(name) - off0) / (h.sum() - sum0))
        assert all(0.0 <= s <= 1.0 for s in shares)
        best = max(shares) if body == "sleep" else min(shares)
        assert lo <= best <= hi, shares

    def test_records_the_kube_trace_span_span_would(self):
        """Same name, same parent, same ring as tracing.span at the
        site; ambient inside the block (RemoteSolver ships it)."""
        fresh()
        ctx = tracing.new_ctx()
        with tracing.phase("wave.solve", parent=ctx, pods=4) as ph:
            assert tracing.current() == ph.ctx
            with tracing.phase("wave.solve.ship"):     # ambient child
                pass
            ph.set(extra=1)
        with tracing.phase("wave.encode", parent=None):  # new root
            pass
        ship, solve_sp, enc = tracing.drain()["spans"]
        assert (solve_sp["name"], solve_sp["tid"], solve_sp["psid"]) == \
            ("wave.solve", ctx[0], ctx[1])
        assert solve_sp["attrs"] == {"pods": 4, "extra": 1}
        assert (ship["name"], ship["tid"], ship["psid"]) == \
            ("wave.solve.ship", ctx[0], solve_sp["sid"])
        assert enc["psid"] == "" and enc["tid"] != ctx[0]
        assert tracing.current() is None

    def test_ambient_default_and_untraced_record_nothing_alone(self):
        """Shared code (batch_solver.solve off the wave loop, an HTTP
        request with no header) keeps time but opens no root trace."""
        fresh()
        h = metrics.Histogram("h", "t", buckets=(1.0,))
        with tracing.phase("wave.solve.route", h):
            pass
        with tracing.phase("http.get", parent=None, traced=False):
            pass
        assert tracing.drain()["spans"] == []
        assert h.count() == 1
        tracing.disable()
        with tracing.phase("wave.solve", h, parent=None) as ph:
            assert ph.ctx is None and tracing.current() is None
        assert h.count() == 2

    def test_cancel_and_since_carry_a_wait_over_empty_ticks(self):
        fresh()
        h = metrics.Histogram("h", "t", buckets=(1.0,))
        since = tracing.clocks()
        with pytest.raises(TimeoutError):
            with tracing.phase("wave.drain.wait", h, parent=None,
                               since=since) as ph:
                time.sleep(0.01)
                ph.cancel()
                raise TimeoutError
        assert h.count() == 0 and tracing.drain()["spans"] == []
        with tracing.phase("wave.drain.wait", h, parent=None, since=since):
            pass
        (sp,) = tracing.drain()["spans"]
        assert h.count() == 1 and h.sum() >= 0.01
        assert sp["t0"] == since[0] and sp["dur"] >= 10_000_000

    def test_cpu_clock_is_read_once_for_adjacent_phases(self, monkeypatch):
        """The thread's CPU clock is a system call (a slow one on the
        benchmark's host): a reading younger than 20 us is carried
        forward, an older one taken anew, and carried ones never chain."""
        reads = []
        real = time.thread_time_ns
        monkeypatch.setattr(time, "thread_time_ns",
                            lambda: reads.append(1) or real())
        tracing._tls.cpu = None
        now = time.monotonic_ns()
        a = tracing._thread_cpu_ns(now)
        b = tracing._thread_cpu_ns(now + 5_000)
        c = tracing._thread_cpu_ns(now + 19_000)
        d = tracing._thread_cpu_ns(now + 25_000)
        assert len(reads) == 2
        assert (b, c) == (a + 5_000, a + 19_000) and d >= a

    def test_imports_and_runs_without_jax(self):
        """An apiserver of the multi-process deployment never loads JAX:
        the helper must not, and then writes no annotation."""
        code = ("import sys\n"
                "from kubernetes_tpu.util import tracing, metrics\n"
                "h = metrics.Histogram('h', 't', buckets=(1.0,))\n"
                "with tracing.phase('wave.solve', h, parent=None):\n"
                "    pass\n"
                "tracing.role('http'); tracing.role_end()\n"
                "assert h.count() == 1\n"
                "assert tracing._annotation is None\n"
                "assert not [m for m in sys.modules if m == 'jax' "
                "or m.startswith('jax.')], 'jax loaded'\n")
        proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                              capture_output=True, text=True, timeout=60)
        assert proc.returncode == 0, proc.stderr[-2000:]

    def test_annotation_lies_on_the_profilers_clock(self, tmp_path):
        """With a jax.profiler session open, the phase is a ktpu/<name>
        event of the host plane, inside an enclosing TraceAnnotation —
        one clock with everything else the profiler records. Its own
        process and time limit: a session is process-wide state."""
        code = (
            "import glob, os, sys, time\n"
            "import jax\n"
            "from jax.profiler import ProfileData, TraceAnnotation\n"
            "from kubernetes_tpu.util import tracing\n"
            "opts = jax.profiler.ProfileOptions()\n"
            "opts.python_tracer_level = 0\n"
            "jax.profiler.start_trace(sys.argv[1], profiler_options=opts)\n"
            "with TraceAnnotation('bench/solve'):\n"
            "    time.sleep(0.002)\n"
            "    with tracing.phase('wave.solve.ship'):\n"
            "        time.sleep(0.002)\n"
            "    with tracing.phase('http.post', detail='pods',\n"
            "                       traced=False):\n"
            "        pass\n"
            "    time.sleep(0.002)\n"
            "jax.profiler.stop_trace()\n"
            "path = glob.glob(os.path.join(sys.argv[1], '**',\n"
            "                 '*.xplane.pb'), recursive=True)[0]\n"
            "host = [p for p in ProfileData.from_file(path).planes\n"
            "        if p.name == '/host:CPU'][0]\n"
            "ev = {e.name: (e.start_ns, e.start_ns + e.duration_ns)\n"
            "      for line in host.lines for e in line.events\n"
            "      if e.name.startswith(('bench/', 'ktpu/'))}\n"
            "assert set(ev) == {'bench/solve', 'ktpu/wave.solve.ship',\n"
            "                   'ktpu/http.post.pods'}, sorted(ev)\n"
            "o0, o1 = ev['bench/solve']\n"
            "s0, s1 = ev['ktpu/wave.solve.ship']\n"
            "assert o0 < s0 and s1 < o1 and s1 - s0 >= 2_000_000, ev\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run([sys.executable, "-c", code, str(tmp_path)],
                              cwd=REPO, env=env, capture_output=True,
                              text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr[-2000:]


# -- the wave loop's sites ----------------------------------------------------

def _bare_scheduler(next_pod, queue=None, **kw):
    """A BatchScheduler over stand-ins: only what _drain_wave and
    _solve_snap touch."""
    import types

    from kubernetes_tpu.scheduler.tpu_batch import BatchScheduler
    config = types.SimpleNamespace(next_pod=next_pod, provider=None,
                                   policy=None, mesh="off")
    factory = types.SimpleNamespace(pod_queue=queue)
    return BatchScheduler(config, factory, client=None, **kw)


def _part(name):
    from kubernetes_tpu.models.batch_solver import wave_parts
    return wave_parts().count(name), wave_parts().sum(name)


class TestWaveSites:
    def test_cpu_wave_observes_each_solve_part_once(self, monkeypatch):
        """One wave through _solve_snap on the CPU (the kernel through
        the interpreter): hostprep, route, ship, launch, readback and
        post observed once each, and together inside the solve's own
        time — with the wave's kube-trace spans hung under wave.solve."""
        from kubernetes_tpu.scheduler import tpu_batch
        monkeypatch.setenv("KTPU_PALLAS", "interpret")
        monkeypatch.setenv("KTPU_PREWARM", "off")
        sched = _bare_scheduler(next_pod=None)
        snap = small_snapshot("parts")
        parts = ["solve.hostprep", "solve.route", "solve.ship",
                 "solve.launch", "solve.readback", "solve.post"]
        solve_h = tpu_batch._wave_metrics().solve
        before = {p: _part(p) for p in parts}
        n0, s0 = solve_h.count(), solve_h.sum()
        names0 = _part("names")[0]
        off0 = _offcpu("wave.solve")
        fresh()
        ctx = tracing.new_ctx()
        decisions = sched._solve_snap(snap, 9, tctx=ctx)
        assert len(decisions.hosts) == 9 and all(decisions.hosts)
        took = solve_h.sum() - s0
        assert solve_h.count() - n0 == 1
        assert all(_part(p)[0] - before[p][0] == 1 for p in parts)
        total = sum(_part(p)[1] - before[p][1] for p in parts)
        assert 0.0 < total <= took
        assert 0.0 <= _offcpu("wave.solve") - off0 <= took
        assert _part("names")[0] - names0 == 1
        spans = {s["name"]: s for s in tracing.drain()["spans"]}
        assert {"wave." + p for p in parts} <= set(spans)
        assert all(spans["wave." + p]["psid"] == spans["wave.solve"]["sid"]
                   for p in parts)
        assert spans["wave.names"]["psid"] == ctx[1]

    def test_drain_counts_why_each_wave_was_cut(self):
        from kubernetes_tpu.client.cache import FIFO
        from kubernetes_tpu.scheduler import tpu_batch
        wm = tpu_batch._wave_metrics()
        queue = FIFO()

        def slow_pop(timeout=None):      # pods keep coming, slowly
            time.sleep(0.004)
            return queue.pop(timeout=timeout)

        def cuts():
            return {r: wm.cut.value(r) for r in ("full", "linger", "empty")}

        for i in range(40):
            queue.add(mk_pod(f"cut-{i}"))
        left0, c0 = wm.queue_left.value(), cuts()
        sched = _bare_scheduler(queue.pop, queue, wave_size=4,
                                wave_linger_s=0.5)
        assert len(sched._drain_wave(0.2)) == 4          # full: 36 left
        sched.config.next_pod = slow_pop
        sched.wave_size, sched.wave_linger_s = 1024, 0.02
        took = len(sched._drain_wave(0.2))               # linger
        assert 1 <= took < 36
        sched.config.next_pod = queue.pop
        sched.wave_linger_s = 0.3
        assert len(sched._drain_wave(0.2)) == 36 - took  # empty: dry queue
        got = {r: v - c0[r] for r, v in cuts().items()}
        assert got == {"full": 1, "linger": 1, "empty": 1}
        assert wm.queue_left.value() - left0 == 36 + (36 - took) + 0

    def test_drain_wait_spans_the_empty_ticks_before_the_pod(self):
        from kubernetes_tpu.client.cache import FIFO
        queue = FIFO()
        sched = _bare_scheduler(queue.pop, queue, wave_linger_s=0.0)
        n0, s0 = _part("drain.wait")
        fresh()
        for _ in range(2):               # two empty ticks: no wave, no span
            with pytest.raises(TimeoutError):
                sched._drain_wave(0.02)
        assert _part("drain.wait")[0] == n0
        assert tracing.drain()["spans"] == []
        queue.add(mk_pod("late"))
        assert len(sched._drain_wave(0.02)) == 1
        n1, s1 = _part("drain.wait")
        assert n1 - n0 == 1 and s1 - s0 >= 0.04   # the whole wait, once
        tctx = sched._wave_ctx([object()])
        spans = tracing.drain()["spans"]
        assert [s["name"] for s in spans] == ["wave.drain.wait",
                                              "wave.drain.collect"]
        assert all((s["tid"], s["psid"]) == tctx for s in spans)
        assert spans[0]["dur"] >= 40_000_000

    def test_fifo_wait_is_observed_once_a_pod_from_its_first_add(self):
        from kubernetes_tpu.client.cache import FIFO
        h = metrics.Histogram("w", "t", buckets=(0.01, 1.0))
        queue = FIFO(wait_hist=h)
        a, b = mk_pod("qa"), mk_pod("qb")
        queue.add(a)
        time.sleep(0.03)
        queue.add(a)                     # coalesced: keeps the first stamp
        queue.add(b)
        assert queue.pop(0.1) is a
        assert h.count() == 1 and h.sum() >= 0.03
        queue.delete(b)                  # never popped: never observed
        with pytest.raises(TimeoutError):
            queue.pop(0.01)
        queue.replace([b])               # a relist stamps what is new
        assert queue.pop(0.1) is b
        assert h.count() == 2 and h.sum() < 0.03 + 0.02
        assert FIFO()._wait_hist is None  # other queues keep no stamps


# -- interpreter time by thread role ------------------------------------------

class TestRoles:
    def _run(self, target):
        t = threading.Thread(target=target)
        t.start()
        t.join(10)
        assert not t.is_alive()

    def test_spinning_thread_shows_under_its_role_and_remark_banks(self):
        seen = {}

        def worker():
            tracing.role("t_role_a")
            _spin(0.05)
            seen["live"] = tracing.role_cpu_seconds()
            tracing.role("t_role_b")     # banks the 50 ms to t_role_a
            seen["after"] = tracing.role_cpu_seconds()
            _spin(0.02)
            tracing.role_end()

        self._run(worker)
        assert seen["live"]["t_role_a"] >= 0.04
        assert seen["after"]["t_role_a"] >= 0.04
        assert seen["after"]["t_role_b"] < 0.01
        done = tracing.role_cpu_seconds()
        assert 0.04 <= done["t_role_a"] < 0.07    # no longer growing
        assert done["t_role_b"] >= 0.015
        assert done["other"] >= 0.0
        assert set(tracing.ROLES) <= set(done)

    def test_thread_that_ended_unmarked_is_dropped_not_fatal(self):
        self._run(lambda: tracing.role("t_role_gone"))
        cpu = tracing.role_cpu_seconds()   # its clock is gone: no raise
        assert cpu.get("t_role_gone", 0.0) < 0.01
        assert all(r[0] != "t_role_gone" for r in tracing._roles.values())

    def test_registry_renders_roles_and_wall_through_the_collector(self):
        self._run(lambda: (tracing.role("t_role_c"), _spin(0.02),
                           tracing.role_end()))
        text = metrics.default_registry().render_text()
        assert 'process_role_cpu_seconds_total{role="t_role_c"} 0.0' in text
        assert 'process_role_cpu_seconds_total{role="other"}' in text
        wall = [ln for ln in text.splitlines()
                if ln.startswith("process_wall_seconds_total ")]
        assert len(wall) == 1 and float(wall[0].split()[1]) > 0.0
        calls = []
        reg = metrics.Registry()
        reg.add_collector(lambda: calls.append(1))
        reg.render_text()
        reg.sample()
        assert len(calls) == 2


# -- overhead guard ----------------------------------------------------------

class TestOverheadGuard:
    def test_disabled_tracing_under_1pct_of_stage_loop(self):
        """The no-op path, costed against a real encode: the wave loop
        has ~10 tracing call sites per wave (drain/prepare/encode/solve/
        commit spans + context reads); 10 disabled calls must cost <1%
        of even the CHEAPEST real stage (one 128-node/256-pod encode —
        a real churn wave at the contract shape is 10k nodes and orders
        of magnitude above it).  Both sides are timed min-of-N so a
        loaded test box (full-suite runs) can't fail the comparison on
        scheduler noise alone."""
        tracing.disable()
        nodes = [mk_node(f"ov-n{i}") for i in range(128)]
        pending = [mk_pod(f"ov-p{j}") for j in range(256)]
        encode_snapshot(nodes, [], pending, [])  # warm the path

        def one_encode():
            t0 = time.perf_counter()
            encode_snapshot(nodes, [], pending, [])
            return time.perf_counter() - t0

        stage_s = min(one_encode() for _ in range(5))

        def noop_waves(n=10_000):
            t0 = time.perf_counter()
            for _ in range(n):
                # one wave's worth of disabled call sites
                with tracing.span("wave.encode"):
                    pass
                with tracing.span("wave.solve"):
                    pass
                with tracing.span("wave.commit"):
                    pass
                with tracing.child_span("store.create"):
                    pass
                tracing.new_ctx()
                tracing.record("wave.drain", 0, 1)
                tracing.record("wave.prepare", 0, 1)
                tracing.current()
                tracing.current()
                tracing.wire()
            return (time.perf_counter() - t0) / n

        per_wave_s = min(noop_waves() for _ in range(5))
        assert per_wave_s < 0.01 * stage_s, (
            f"disabled tracing {per_wave_s * 1e6:.2f}us/wave vs stage "
            f"{stage_s * 1e3:.2f}ms — over the 1% budget")

    def test_always_on_phases_under_1pct_of_a_wave(self):
        """What tracing.phase costs with kube-trace OFF and no profiler
        session — the state of every production run — costed like the
        disabled path above, min-of-N on both sides. A phase is two clock
        pairs, a histogram observe, a counter add and an inert
        annotation, so it cannot be free like the no-op span: ONE call
        must stay under 1% of the cheapest real encode, and one wave's
        worth of them (16: the table in docs/design/observability.md)
        under 1% of the cheapest wave cycle the loop runs below
        capacity — the default 20 ms linger plus that encode (at
        capacity a wave is ~100 pods and its cycle ten times that)."""
        import inspect

        from kubernetes_tpu.scheduler.tpu_batch import BatchScheduler
        linger_s = inspect.signature(
            BatchScheduler.__init__).parameters["wave_linger_s"].default
        tracing.disable()
        nodes = [mk_node(f"ov-n{i}") for i in range(128)]
        pending = [mk_pod(f"ov-p{j}") for j in range(256)]
        encode_snapshot(nodes, [], pending, [])  # warm the path

        def one_encode():
            t0 = time.perf_counter()
            encode_snapshot(nodes, [], pending, [])
            return time.perf_counter() - t0

        stage_s = min(one_encode() for _ in range(5))
        # histograms of the real ones' shapes, outside the registry
        buckets = (0.001, 0.0025, 0.005, 0.01, 0.02, 0.05, 0.1, 0.25, 0.5,
                   1.0, 2.5)
        part = metrics.Histogram("p", "t", ("part",), buckets=buckets)
        stage = metrics.Histogram("s", "t", buckets=buckets)
        parts = ["drain.wait", "drain.collect", "prepare", "solve.hostprep",
                 "solve.route", "solve.ship", "solve.launch",
                 "solve.readback", "solve.post", "names", "commit.build",
                 "commit.bind", "commit.assume"]

        def helper_waves(n=2_000):
            t0 = time.perf_counter()
            for _ in range(n):
                for label in parts:
                    with tracing.phase("t.ovh." + label, part, label):
                        pass
                for name in ("t.ovh.encode", "t.ovh.solve", "t.ovh.commit"):
                    with tracing.phase(name, stage, parent=None):
                        pass
            return (time.perf_counter() - t0) / n

        per_wave_s = min(helper_waves() for _ in range(5))
        per_call_s = per_wave_s / (len(parts) + 3)
        assert stage.count() == 5 * 2_000 * 3
        assert per_call_s < 0.01 * stage_s, (
            f"one always-on phase {per_call_s * 1e6:.2f}us vs encode "
            f"{stage_s * 1e3:.2f}ms — over the 1% budget")
        assert per_wave_s < 0.01 * (linger_s + stage_s), (
            f"always-on phases {per_wave_s * 1e6:.1f}us/wave vs a "
            f"{(linger_s + stage_s) * 1e3:.1f}ms wave cycle — over 1%")


# -- Histogram.quantile semantics (the latency record contract) --------------

class TestQuantileSemantics:
    def _hist(self, buckets=(0.1, 1.0, 10.0)):
        return metrics.Histogram("h", "t", buckets=buckets)

    def test_empty_histogram_has_no_quantiles(self):
        h = self._hist()
        assert h.quantile(0.5) is None     # None, never a fake 0.0

    def test_single_bucket_reports_its_upper_bound(self):
        h = self._hist()
        for _ in range(5):
            h.observe(0.05)                # all in the first bucket
        for q in (0.0, 0.01, 0.5, 0.99, 1.0):
            assert h.quantile(q) == 0.1    # interpolation-free bound

    def test_quantile_is_always_a_configured_bound(self):
        h = self._hist()
        for v in (0.05, 0.5, 0.5, 5.0):
            h.observe(v)
        assert h.quantile(0.25) == 0.1
        assert h.quantile(0.5) == 1.0      # conservative upper bound
        assert h.quantile(0.75) == 1.0
        assert h.quantile(0.99) == 10.0

    def test_overflow_is_inf_not_a_trustworthy_number(self):
        h = self._hist()
        h.observe(50.0)                    # beyond the largest bound
        assert h.quantile(0.5) == float("inf")

    def test_tiny_q_clamps_to_first_nonempty_bucket(self):
        h = self._hist()
        h.observe(5.0)                     # only the 10.0 bucket
        assert h.quantile(0.0) == 10.0     # not buckets[0]


class TestPodLatencyMetrics:
    def test_histograms_register_and_render(self):
        reg = metrics.Registry()
        m = metrics.PodLatencyMetrics(registry=reg)
        m.e2e.observe(0.4)
        m.watch_observe.observe(0.05)
        text = reg.render_text()
        assert "pod_e2e_scheduling_seconds_bucket" in text
        assert "pod_watch_observe_seconds_count 1" in text
        assert m.e2e.quantile(0.5) == 0.5  # POD_E2E_BUCKETS bound
