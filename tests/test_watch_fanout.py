"""One writer for every chunked watch stream (apiserver/watchwriter.py):
each stream still gets exactly its own frames, in store order, byte for
byte; one thread writes them all; a stuck client holds no one up and still
ends through its lag bound; a client that hangs up is let go; the writer's
two counters count wakes and streams written."""

import json
import socket
import threading
import time

import pytest

from kubernetes_tpu import watch as watchpkg
from kubernetes_tpu.api import types as api
from kubernetes_tpu.api.quantity import Quantity
from kubernetes_tpu.apiserver.http import APIServer
from kubernetes_tpu.apiserver.master import Master, MasterConfig
from kubernetes_tpu.apiserver.watchwriter import WatchWriter
from kubernetes_tpu.client.client import Client
from kubernetes_tpu.client.http import HTTPTransport
from kubernetes_tpu.util import metrics as metrics_pkg

NAMESPACES = [f"ns-{i:02d}" for i in range(50)]


def make_pod(name, ns="default", note=""):
    return api.Pod(
        metadata=api.ObjectMeta(name=name, namespace=ns,
                                annotations={"note": note} if note else {}),
        spec=api.PodSpec(containers=[api.Container(
            name="c", image="img",
            resources=api.ResourceRequirements(
                limits={"cpu": Quantity("100m"),
                        "memory": Quantity("64Mi")}))]))


def binding(name, ns, host):
    return api.Binding(metadata=api.ObjectMeta(name=name, namespace=ns),
                       pod_name=name, host=host)


class RawWatch:
    """A chunked watch read from the socket as the server wrote it."""

    def __init__(self, port, path, rcvbuf=0):
        # one at a time, head read: a burst of connects overruns the
        # server's listen backlog and waits out SYN retries
        self.sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        if rcvbuf:
            self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
        self.sock.connect(("127.0.0.1", port))
        self.sock.sendall(f"GET {path} HTTP/1.1\r\nHost: t\r\n\r\n".encode())
        self.f = self.sock.makefile("rb")
        self.sock.settimeout(10.0)
        while self.f.readline() not in (b"\r\n", b""):
            pass

    def read_bytes(self, n, timeout=10.0):
        self.sock.settimeout(timeout)
        return self.f.read(n)

    def read_frame(self, timeout=10.0):
        """One chunk's payload, or None at the terminating chunk or EOF."""
        self.sock.settimeout(timeout)
        size = self.f.readline()
        if not size:
            return None
        if int(size.strip(), 16) == 0:
            self.f.readline()                 # the chunk's closing CRLF
            return None
        data = self.f.read(int(size.strip(), 16))
        self.f.readline()
        return data

    def close(self):
        try:
            self.f.close()
            self.sock.close()
        except OSError:
            pass


def wait_for(cond, timeout=10.0, what="condition"):
    deadline = time.monotonic() + timeout
    while not cond():
        if time.monotonic() > deadline:
            raise AssertionError(f"timed out waiting for {what}")
        time.sleep(0.005)


def registered(srv):
    return len(srv.watch_writer._streams)


def writer_threads():
    return [t for t in threading.enumerate() if t.name == "watch-writer"]


@pytest.fixture()
def srv():
    server = APIServer(Master(MasterConfig())).start()
    yield server
    server.stop()


def test_sixty_streams_over_fifty_namespaces_get_their_frames_in_store_order(
        srv):
    # a reader of the same store history, as one stream of its own would
    # have been written: every pod event, in store order, through the same
    # frame cache
    ref, translate = srv.master.dispatch(
        "watch_raw", "pods", namespace="", label_selector="",
        field_selector="", resource_version="", user=None, lag_limit=None)
    paths = [f"/api/v1/namespaces/{ns}/pods?watch=1" for ns in NAMESPACES]
    paths += [f"/api/v1/namespaces/{ns}/pods?watch=1"
              for ns in NAMESPACES[:5]]                 # a second reader
    paths += ["/api/v1/pods?watch=1"] * 5               # every namespace
    streams = [RawWatch(srv.port, p) for p in paths]
    try:
        wait_for(lambda: registered(srv) == 60, what="60 registrations")
        client = Client(HTTPTransport(srv.base_url))
        made = []
        for i in range(120):
            ns = NAMESPACES[(i * 7) % 50]
            client.pods(ns).create(make_pod(f"p-{i:03d}", ns))
            made.append((f"p-{i:03d}", ns))
            if i % 3 == 2:                               # one bind a time
                name, bns = made[i - 2]
                client.pods(bns).bind(binding(name, bns, f"m-{i}"))
            if i % 10 == 9:                              # and a batch
                name, bns = made[i - (i - 1) % 3]
                client.pods(bns).bind_many(api.BindingList(
                    items=[binding(name, bns, f"n-{i}")]))
        events = []
        while True:
            got, _ = ref.drain(1 << 16)
            events += got
            if len(events) >= 120 + 40 + 12:
                break
            time.sleep(0.01)
        want = {ns: b"" for ns in NAMESPACES}
        everything = b""
        for ev in events:
            parts, lagged = srv.translate_batch([ev], translate, "v1",
                                                ws_frames=False)
            assert not lagged and len(parts) == 1
            frame = json.loads(parts[0].split(b"\r\n", 1)[1])
            want[frame["object"]["metadata"]["namespace"]] += parts[0]
            everything += parts[0]
        for path, s in zip(paths, streams):
            ns = path.split("/")[4] if "/namespaces/" in path else None
            expect = everything if ns is None else want[ns]
            assert s.read_bytes(len(expect)) == expect, path
        # and not one byte more
        for s in streams:
            s.sock.settimeout(0.05)
            with pytest.raises(socket.timeout):
                s.sock.recv(1)
    finally:
        ref.stop()
        for s in streams:
            s.close()


def test_one_writer_thread_serves_every_stream(srv):
    before = len(writer_threads())
    streams = []
    try:
        streams.append(RawWatch(srv.port, "/api/v1/pods?watch=1"))
        wait_for(lambda: registered(srv) == 1, what="the first stream")
        assert len(writer_threads()) == before + 1
        for ns in NAMESPACES[:30]:
            streams.append(RawWatch(
                srv.port, f"/api/v1/namespaces/{ns}/pods?watch=1"))
        wait_for(lambda: registered(srv) == 31, what="31 streams")
        client = Client(HTTPTransport(srv.base_url))
        for ns in NAMESPACES[:30]:
            client.pods(ns).create(make_pod("one", ns))
        for s in streams[1:]:
            assert json.loads(s.read_frame())["type"] == "ADDED"
        assert len(writer_threads()) == before + 1
    finally:
        for s in streams:
            s.close()
    srv.stop()
    # the writer goes with its server
    wait_for(lambda: len(writer_threads()) == before, what="the writer")


def test_a_stuck_client_does_not_delay_a_fast_stream_and_ends_with_410():
    srv = APIServer(Master(MasterConfig()), watch_lag_limit=64).start()
    stuck = fast = None
    resyncs = metrics_pkg.default_registry().counter(
        "watch_lag_resyncs_total")
    resyncs0 = resyncs.total()
    try:
        # a client that reads nothing after the head, over a small window
        stuck = RawWatch(srv.port, "/api/v1/pods?watch=1", rcvbuf=4096)
        wait_for(lambda: registered(srv) == 1, what="the stuck stream")
        (stream,) = srv.watch_writer._streams
        stream.sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
        fast = RawWatch(srv.port, "/api/v1/pods?watch=1")
        wait_for(lambda: registered(srv) == 2, what="the fast stream")
        client = Client(HTTPTransport(srv.base_url))
        note = "x" * 4096
        for i in range(400):
            client.pods().create(make_pod(f"s-{i:03d}", note=note))
            t0 = time.monotonic()
            frame = json.loads(fast.read_frame(timeout=5.0))
            assert frame["object"]["metadata"]["name"] == f"s-{i:03d}"
            # a linger and a write, whatever the stuck stream holds
            assert time.monotonic() - t0 < 2.0
            if resyncs.total() > resyncs0:
                break
        assert resyncs.total() == resyncs0 + 1, "the stuck stream never lagged"
        assert stream.pending is not None        # its socket was full
        # the stuck client reads again: what was written, then the 410
        frames = []
        while True:
            f = stuck.read_frame(timeout=10.0)
            if f is None:
                break
            frames.append(json.loads(f))
        assert frames[-1]["type"] == "ERROR"
        assert frames[-1]["object"]["code"] == 410
        assert frames[-1]["object"]["reason"] == "Expired"
        assert [f["type"] for f in frames[:-1]] == ["ADDED"] * (len(frames) - 1)
        assert srv.metric_watch_lag_drops.total() == 1
        wait_for(lambda: registered(srv) == 1, what="the stuck stream's end")
    finally:
        for s in (stuck, fast):
            if s is not None:
                s.close()
        srv.stop()


def test_a_client_that_hangs_up_frees_its_registration_and_handler(srv):
    def handlers():
        return [t for t in threading.enumerate()
                if "process_request_thread" in t.name]

    before = len(handlers())
    watches = [RawWatch(srv.port, "/api/v1/pods?watch=1") for _ in range(3)]
    wait_for(lambda: registered(srv) == 3, what="three streams")
    assert len(handlers()) == before + 3
    assert len(srv._watchers) == 3
    watches[0].close()
    watches[1].close()
    # no event needed: the parked handler sees the hang-up itself
    wait_for(lambda: registered(srv) == 1, what="the hung-up streams' end")
    wait_for(lambda: len(handlers()) == before + 1, what="their handlers")
    wait_for(lambda: len(srv._watchers) == 1, what="their watchers")
    # the one left still streams
    Client(HTTPTransport(srv.base_url)).pods().create(make_pod("after"))
    frame = json.loads(watches[2].read_frame())
    assert frame["object"]["metadata"]["name"] == "after"
    watches[2].close()


def test_a_server_stop_ends_each_stream_with_the_terminating_chunk():
    srv = APIServer(Master(MasterConfig())).start()
    w = RawWatch(srv.port, "/api/v1/pods?watch=1")
    try:
        wait_for(lambda: registered(srv) == 1, what="the stream")
        Client(HTTPTransport(srv.base_url)).pods().create(make_pod("last"))
        assert json.loads(w.read_frame())["type"] == "ADDED"
        srv.stop()
        assert w.read_frame() is None               # "0\r\n\r\n"
        assert w.read_bytes(1) == b""               # and the close
    finally:
        w.close()


class StubServer:
    """What the writer asks of its server: a linger, a translate and the
    fan-out metrics. Each event is its own 2-byte frame."""

    def __init__(self):
        reg = metrics_pkg.Registry()
        self.watch_write_linger = 0.0
        self.metric_fanout_wakes = reg.counter("wakes", "")
        self.metric_fanout_streams_written = reg.counter("streams", "")
        self.metric_fanout_frames = reg.histogram("frames", "")
        self.metric_fanout_seconds = reg.histogram("seconds", "")

    def translate_batch(self, batch, translate, version, ws_frames):
        return [b"%d;" % ev.object for ev in batch], False


def test_ten_streams_ready_in_one_linger_are_one_wake_and_ten_writes():
    stub = StubServer()
    writer = WatchWriter(stub)
    pairs = [socket.socketpair() for _ in range(10)]
    watchers = [watchpkg.Watcher() for _ in pairs]
    handlers = [threading.Thread(target=writer.serve,
                                 args=(w, None, "v1", a), daemon=True)
                for w, (a, _b) in zip(watchers, pairs)]
    try:
        for t in handlers:
            t.start()
        wait_for(lambda: len(writer._streams) == 10, what="10 streams")
        wait_for(lambda: not writer._ready, what="the writer to idle")
        time.sleep(0.05)
        stub.watch_write_linger = 0.3
        for i, w in enumerate(watchers):
            w.send(watchpkg.Event(watchpkg.ADDED, i))
            w.send(watchpkg.Event(watchpkg.ADDED, 10 + i))
        for i, (_a, b) in enumerate(pairs):
            b.settimeout(5.0)
            want = b"%d;%d;" % (i, 10 + i)
            got = b""
            while len(got) < len(want):
                got += b.recv(64)
            assert got == want
        assert stub.metric_fanout_wakes.total() == 1
        assert stub.metric_fanout_streams_written.total() == 10
        assert stub.metric_fanout_frames.count() == 10
        # one more event on one stream: one more wake, one more stream
        stub.watch_write_linger = 0.0
        watchers[3].send(watchpkg.Event(watchpkg.ADDED, 99))
        assert pairs[3][1].recv(64) == b"99;"
        wait_for(lambda: stub.metric_fanout_wakes.total() == 2,
                 what="the second wake")
        assert stub.metric_fanout_streams_written.total() == 11
    finally:
        for w in watchers:
            w.stop()
        writer.stop()
        for t in handlers:
            t.join(timeout=5.0)
        for a, b in pairs:
            a.close()
            b.close()
    assert not any(t.is_alive() for t in handlers)


def test_a_watchers_ready_hook_runs_once_a_batch():
    marks = []
    w = watchpkg.Watcher(lag_limit=4)
    w.set_ready_hook(marks.append)
    w.send(watchpkg.Event(watchpkg.ADDED, 1))
    w.send(watchpkg.Event(watchpkg.ADDED, 2))
    assert marks == [w]                       # once, not once an event
    assert w.drain(1) == ([watchpkg.Event(watchpkg.ADDED, 1)], False)
    assert w.queued() == 1
    w.send(watchpkg.Event(watchpkg.ADDED, 3))
    assert len(marks) == 2                    # the drain cleared the mark
    events, ended = w.drain()
    assert [ev.object for ev in events] == [2, 3] and not ended
    for i in range(5):                        # past the lag bound
        w.send(watchpkg.Event(watchpkg.ADDED, 10 + i))
    assert len(marks) == 3
    events, ended = w.drain()
    assert [ev.type for ev in events] == [watchpkg.ERROR] and ended
    assert w.lagged
    w.send(watchpkg.Event(watchpkg.ADDED, 99))   # a stopped watcher
    assert w.drain() == ([], True)
    other = watchpkg.Watcher()
    other.set_ready_hook(marks.append)
    other.close()                             # the end is news too
    assert marks[-1] is other and other.drain() == ([], True)
