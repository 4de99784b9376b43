"""util/reqparts.py through the apiserver's handler: every request's wall
is split by part at the boundaries the handler, the master, the registry
and the store helper cross, and lands once, when the request ends, in
``apiserver_request_part_seconds_total`` and
``apiserver_request_offcpu_seconds_total``. The handler is driven with its
socket stubbed (bytes in, bytes out), so the clock the test holds round a
request sees what the parts see and no network.
"""

import io
import json
import time

import pytest

from benchmarks.readers import promtext
from kubernetes_tpu.api import types as api
from kubernetes_tpu.apiserver import http as httpmod
from kubernetes_tpu.apiserver.master import Master, MasterConfig
from kubernetes_tpu.util import reqparts

NAMES = [part for part, _group in reqparts.PARTS]


def pod(name: str) -> dict:
    return {"kind": "Pod", "apiVersion": "v1", "metadata": {"name": name},
            "spec": {"containers": [{
                "name": "c", "image": "i", "resources": {
                    "limits": {"cpu": "100m", "memory": "500Mi"}}}]}}


def request(method: str, path: str, body=None) -> bytes:
    payload = json.dumps(body).encode() if body is not None else b""
    return (f"{method} {path} HTTP/1.1\r\nHost: test\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(payload)}\r\n\r\n").encode() + payload


def create(name: str) -> bytes:
    return request("POST", "/api/v1/namespaces/default/pods", pod(name))


def bind(names: list) -> bytes:
    return request("POST", "/api/v1/namespaces/default/bindings:batch", {
        "kind": "BindingList", "apiVersion": "v1",
        "items": [{"metadata": {"name": n}, "podName": n, "host": "node-1"}
                  for n in names]})


class Stubbed:
    """An APIServer that never listens, and handlers of it whose socket is
    two byte buffers."""

    def __init__(self, **config):
        self.api = httpmod.APIServer(Master(MasterConfig(**config)))

    def close(self) -> None:
        self.api._httpd.server_close()

    def serve(self, requests: list) -> tuple:
        """The requests through one handler, as one kept-alive connection
        would bring them: (seconds on the test's clock, response bytes)."""
        h = object.__new__(httpmod._Handler)
        h.rfile = io.BytesIO(b"".join(requests))
        h.wfile = io.BytesIO()
        h.client_address = ("127.0.0.1", 0)
        h.server = self
        wall = 0.0
        for _ in requests:
            # handle_one_request(), with the clock where the handler's own
            # code begins: the first line has arrived
            h.raw_requestline = h.rfile.readline(65537)
            t0 = time.perf_counter()
            assert h.parse_request()
            getattr(h, "do_" + h.command)()
            wall += time.perf_counter() - t0
        return wall, h.wfile.getvalue()

    def parts(self, verb: str, resource: str) -> dict:
        """The rendered seconds of one verb and resource, by part, with
        ``offcpu`` and the request count."""
        text = self.api.metrics_registry.render_text()
        labels = {"verb": verb, "resource": resource}
        out = {part: promtext.total(
            text, "apiserver_request_part_seconds_total",
            dict(labels, part=part)) for part in NAMES}
        out["offcpu"] = promtext.total(
            text, "apiserver_request_offcpu_seconds_total", labels)
        out["count"] = promtext.total(
            text, "apiserver_request_latencies_seconds_count", labels)
        return out


@pytest.fixture
def stubbed():
    s = Stubbed()
    yield s
    s.close()


def creates(stubbed, n: int):
    wall, out = stubbed.serve([create(f"p{i}") for i in range(n)])
    assert out.count(b" 201 ") == n
    return wall, ("post", "pods")


def bind_batches(stubbed, n: int):
    for i in range(n * 8):      # in process: no HTTP request, no parts
        stubbed.api.master.dispatch(
            "create", "pods", namespace="default",
            body=stubbed.api.scheme.decode(json.dumps(pod(f"p{i}"))))
    assert stubbed.parts("post", "pods")["count"] == 0
    wall, out = stubbed.serve([
        bind([f"p{i}" for i in range(j * 8, j * 8 + 8)]) for j in range(n)])
    assert out.count(b" 200 ") == n and b'"error"' not in out
    return wall, ("post", "bindings:batch")


def gets(stubbed, n: int):
    # a list of twenty: a GET of one pod is so short that the fold of its
    # own parts, which no part can hold, is a twentieth of it
    for i in range(20):
        stubbed.api.master.dispatch(
            "create", "pods", namespace="default",
            body=stubbed.api.scheme.decode(json.dumps(pod(f"p{i}"))))
    wall, out = stubbed.serve(
        [request("GET", "/api/v1/namespaces/default/pods")] * n)
    assert out.count(b" 200 ") == n
    return wall, ("get", "pods")


@pytest.mark.parametrize("drive,spent,unspent", [
    (creates, ("read", "decode", "admit", "validate", "walk", "store",
               "encode", "send", "other"), ()),
    (bind_batches, ("read", "decode", "admit", "validate", "walk", "store",
                    "encode", "send", "other"), ()),
    (gets, ("read", "admit", "store", "encode", "send", "other"),
     ("decode", "validate", "walk")),
], ids=["create", "bind_batch", "get"])
def test_the_parts_sum_to_the_requests_wall(stubbed, drive, spent, unspent):
    n = 200
    wall, key = drive(stubbed, n)
    got = stubbed.parts(*key)
    assert got["count"] == n
    for part in spent:
        assert got[part] > 0.0, part
    for part in unspent:
        assert got[part] == 0.0, part
    total = sum(got[part] for part in NAMES)
    assert total <= wall
    assert total >= 0.95 * wall
    # nothing blocks behind a stubbed socket, but a shared machine takes
    # the core away when it likes: only the bounds hold
    assert 0.0 <= got["offcpu"] <= total


def test_a_create_is_mostly_codec_and_its_groups_read_through_promtext(
        stubbed):
    """What the benchmark's metric files read: a group named, its parts
    summed; no label named, everything."""
    creates(stubbed, 50)
    text = stubbed.api.metrics_registry.render_text()
    series = "apiserver_request_part_seconds_total"
    post = {"verb": "post", "resource": "pods"}
    got = stubbed.parts("post", "pods")
    by_group = {g: promtext.total(text, series, dict(post, group=g))
                for g in ("http", "codec", "rules", "store", "other")}
    assert by_group["codec"] == pytest.approx(
        got["decode"] + got["walk"] + got["encode"])
    assert by_group["http"] == pytest.approx(got["read"] + got["send"])
    assert by_group["rules"] == pytest.approx(got["admit"] + got["validate"])
    assert sum(by_group.values()) == pytest.approx(
        promtext.total(text, series, post))
    # the largest of the handler's own work (on a busy machine ``other``
    # also holds the waits for a core: the kernel takes the thread off at
    # the CPU clock's system call, the last thing before the last mark)
    assert by_group["codec"] > max(by_group[g] for g in ("http", "rules",
                                                         "store"))


def test_a_failing_admission_still_closes_its_parts():
    denied = Stubbed(admission_control=("AlwaysDeny",))
    try:
        wall, out = denied.serve([create("p0")])
        assert b" 403 " in out
        got = denied.parts("post", "pods")
        assert got["count"] == 1
        for part in ("read", "decode", "admit", "encode", "send"):
            assert got[part] > 0.0, part       # encode: the Status
        for part in ("validate", "walk", "store"):
            assert got[part] == 0.0, part      # it never got that far
        assert 0.9 * wall <= sum(got[p] for p in NAMES) <= wall
        # and the next request of the connection starts from nothing
        wall, out = denied.serve(
            [request("GET", "/api/v1/namespaces/default/pods")] * 20)
        assert out.count(b" 200 ") == 20
        lists = denied.parts("get", "pods")
        assert lists["count"] == 20 and lists["decode"] == 0.0
        assert 0.9 * wall <= sum(lists[p] for p in NAMES) <= wall
        assert denied.parts("post", "pods") == got
    finally:
        denied.close()


def test_a_caller_that_is_no_http_request_marks_nothing(stubbed):
    """The in-process client's dispatch carries NO_PARTS down the same
    code: nothing is counted, nothing fails."""
    stubbed.api.master.dispatch(
        "create", "pods", namespace="default",
        body=stubbed.api.scheme.decode(json.dumps(pod("p0"))))
    out = stubbed.api.master.bind_batch("default", api.BindingList(items=[
        api.Binding(pod_name="p0", host="node-1")]))
    assert not out.items[0].error
    text = stubbed.api.metrics_registry.render_text()
    assert "apiserver_request_part_seconds_total{" not in text


def test_a_connection_reads_its_cpu_clock_once_a_request(stubbed,
                                                         monkeypatch):
    """The reading a request ends with is the next one's start: the
    thread used no CPU while it waited for the next first line."""
    readings = []
    real = reqparts.thread_time_ns

    def counted():
        readings.append(real())
        return readings[-1]

    monkeypatch.setattr(reqparts, "thread_time_ns", counted)
    creates(stubbed, 5)
    assert len(readings) == 6           # the first request takes two
    creates_again, _ = stubbed.serve([create("q0")])   # a new connection
    assert len(readings) == 8


def test_off_cpu_time_survives_a_cpu_clock_that_ticks_coarsely(stubbed,
                                                               monkeypatch):
    """The benchmark's host ticks its thread CPU clock in 10 ms, forty
    creates long: most requests read no CPU at all and a few read a whole
    tick. Clamped a request, their sum would call a thread that never
    waited nine tenths idle; added as they are, the ticks cancel."""
    real = reqparts.thread_time_ns
    tick = 10_000_000
    monkeypatch.setattr(reqparts, "thread_time_ns",
                        lambda: real() // tick * tick)
    cpu0 = real()
    wall, key = creates(stubbed, 300)
    cpu = (real() - cpu0) * 1e-9        # this thread served them
    assert wall > 3 * tick * 1e-9       # several ticks' worth of requests
    got = stubbed.parts(*key)
    waited = sum(got[part] for part in NAMES) - cpu
    assert abs(got["offcpu"] - max(0.0, waited)) <= 2 * tick * 1e-9


# What a request may pay for its parts, in bare clock readings: this
# sandbox runs the same code at 5 us one minute and 9 the next, and the
# clock with it (0.07-0.11 us a reading), so the bound is a ratio. A
# create's eleven marks, its thread-CPU reading (0.33 us) and the fold
# into the totals cost 4.5 times eleven bare readings in a loop
# (PERF.md §6, PR 38: 5 us where the issue budgeted 3); a
# Counter.inc() a part or a tracing.phase a boundary would double that
# and more.
BUDGET_READINGS = 8.0


def test_the_added_cost_a_request_is_under_budget(stubbed, monkeypatch):
    """10,000 creates' worth of marks, replayed: the handler is driven
    once with a RequestParts that writes down what is asked of it, and
    that is then asked of the real one with nothing else on the clock
    (through the whole handler the cost drowns in the run-to-run noise
    of a request two orders longer)."""
    asked = []

    class Recording(reqparts.RequestParts):
        __slots__ = ()

        def mark(self, part):
            asked.append(part)
            super().mark(part)

        def end(self, totals, verb, resource):
            asked.append((verb, resource))
            super().end(totals, verb, resource)

    monkeypatch.setattr(reqparts, "RequestParts", Recording)
    creates(stubbed, 1)
    monkeypatch.undo()
    # end() marks once itself: everything before the key is the handler's
    key = asked.index(("post", "pods"))
    marks, after = asked[:key], asked[key + 1:]
    assert after == [reqparts.OTHER]
    assert marks == [reqparts.OTHER, reqparts.DECODE, reqparts.OTHER,
                     reqparts.ADMIT, reqparts.VALIDATE, reqparts.WALK,
                     reqparts.STORE, reqparts.OTHER, reqparts.ENCODE,
                     reqparts.SEND, reqparts.OTHER]

    totals = stubbed.api.request_parts
    clock = time.perf_counter_ns
    best = bare = float("inf")
    for _ in range(20):         # the quietest 500 of 10,000 speak
        t0 = time.perf_counter()
        cpu_ns = None           # a kept-alive connection, as a feeder's
        for _ in range(500):
            parts = reqparts.RequestParts(cpu_ns)
            for part in marks:
                parts.mark(part)
            cpu_ns = parts.end(totals, "post", "pods")
        t1 = time.perf_counter()
        for _ in range(500):
            for part in marks:
                clock()
        bare = min(bare, (time.perf_counter() - t1) / 500)
        best = min(best, (t1 - t0) / 500)
    assert best < BUDGET_READINGS * bare, (best, bare)
    assert stubbed.parts("post", "pods")["count"] == 1   # only the real one
