"""HTTP serving layer: REST routes, watch streaming, auth, metrics.

Mirrors the reference's apiserver tests (pkg/apiserver/apiserver_test.go,
watch_test.go) and the integration auth matrix (test/integration/auth_test.go)
— here against a live in-process HTTP server with real sockets.
"""

import json
import urllib.request

import pytest

from kubernetes_tpu import watch as watchpkg
from kubernetes_tpu.api import errors
from kubernetes_tpu.api import types as api
from kubernetes_tpu.api.quantity import Quantity
from kubernetes_tpu.apiserver.http import APIServer
from kubernetes_tpu.apiserver.master import Master, MasterConfig
from kubernetes_tpu.auth import (AuthRequest, BasicAuthAuthenticator,
                                 TokenAuthenticator, UnionAuthenticator,
                                 UserInfo, load_password_file, load_token_file)
from kubernetes_tpu.auth.abac import ABACAuthorizer
from kubernetes_tpu.client.client import Client
from kubernetes_tpu.client.http import HTTPTransport


def make_pod(name="p1", ns="default", labels=None):
    return api.Pod(
        metadata=api.ObjectMeta(name=name, namespace=ns, labels=labels or {}),
        spec=api.PodSpec(containers=[api.Container(
            name="c", image="img",
            resources=api.ResourceRequirements(
                limits={"cpu": Quantity("100m"), "memory": Quantity("64Mi")}))]))


@pytest.fixture()
def server():
    srv = APIServer(Master(MasterConfig())).start()
    yield srv
    srv.stop()


@pytest.fixture()
def client(server):
    return Client(HTTPTransport(server.base_url))


class TestCRUD:
    def test_create_get_list_delete(self, client):
        created = client.pods().create(make_pod("web-1", labels={"app": "web"}))
        assert created.metadata.uid
        assert created.metadata.resource_version

        got = client.pods().get("web-1")
        assert got.metadata.name == "web-1"
        assert got.metadata.self_link.endswith("/namespaces/default/pods/web-1")

        lst = client.pods().list(label_selector="app=web")
        assert [p.metadata.name for p in lst.items] == ["web-1"]
        assert client.pods().list(label_selector="app=db").items == []

        client.pods().delete("web-1")
        with pytest.raises(errors.StatusError) as ei:
            client.pods().get("web-1")
        assert errors.is_not_found(ei.value)

    def test_update_conflict(self, client):
        client.pods().create(make_pod("u1"))
        got = client.pods().get("u1")
        got.metadata.labels = {"v": "2"}
        updated = client.pods().update(got)
        assert updated.metadata.labels == {"v": "2"}
        # stale resourceVersion -> conflict
        got.metadata.resource_version = "1"
        with pytest.raises(errors.StatusError) as ei:
            client.pods().update(got)
        assert errors.is_conflict(ei.value)

    def test_cluster_scoped_nodes(self, client):
        client.nodes().create(api.Node(
            metadata=api.ObjectMeta(name="n1"),
            spec=api.NodeSpec(capacity={"cpu": Quantity("4")})))
        assert client.nodes().get("n1").metadata.self_link == "/api/v1/nodes/n1"

    def test_binding_subresource(self, client, server):
        client.nodes().create(api.Node(metadata=api.ObjectMeta(name="n1"),
                                       spec=api.NodeSpec(capacity={})))
        client.pods().create(make_pod("b1"))
        client.pods().bind(api.Binding(pod_name="b1", host="n1",
                                       metadata=api.ObjectMeta(namespace="default")))
        assert client.pods().get("b1").spec.host == "n1"

    def test_patch(self, client):
        client.pods().create(make_pod("pp", labels={"a": "1"}))
        # a merge patch is expressed in the wire shape of the version it is
        # POSTed against — v1beta1 flattens labels to the top level
        # (ref: resthandler.go PatchResource patches the versioned object)
        if client.transport.version in ("v1beta1", "v1beta2"):
            body = {"labels": {"b": "2"}}
        else:
            body = {"metadata": {"labels": {"b": "2"}}}
        out = client.transport.request(
            "patch", "pods", namespace="default", name="pp", body=body)
        assert out.metadata.labels == {"a": "1", "b": "2"}

    def test_keepalive_survives_delete_with_body(self, server):
        # unread request bodies must be drained or the next request on the
        # same keep-alive connection desyncs
        import http.client
        conn = http.client.HTTPConnection("127.0.0.1", server.port)
        conn.request("DELETE", "/api/v1/namespaces/default/pods/nope",
                     body=b'{"kind":"DeleteOptions"}')
        r1 = conn.getresponse()
        r1.read()
        assert r1.status == 404
        conn.request("GET", "/api/v1/namespaces/default/pods")
        r2 = conn.getresponse()
        assert r2.status == 200  # connection still in sync
        r2.read()
        conn.close()

    def test_transport_retries_dead_keptalive_connection(self, client,
                                                         server):
        # a server may close an idle kept-alive connection between our
        # requests; the transport must retry once on a fresh connection
        # instead of surfacing the transport error
        client.pods().create(make_pod("ka-retry"))
        conn = client.transport._conn()
        conn.sock.close()       # simulate server-side idle close
        got = client.pods().get("ka-retry")
        assert got.metadata.name == "ka-retry"

    def test_transport_retries_post_when_send_fails(self, client):
        # send-phase failure (request never fully written): safe to retry
        # even for non-idempotent verbs, as Go's http.Transport does. The
        # socket stays healthy so the _conn probe passes and the failure
        # genuinely exercises the sent=False branch of the retry loop.
        client.pods().list()
        conn = client.transport._conn()

        def die_mid_write(*a, **kw):
            raise BrokenPipeError("request died mid-write")

        conn.request = die_mid_write
        created = client.pods().create(make_pod("ka-post"))
        assert created.metadata.name == "ka-post"

    def test_transport_no_retry_nonidempotent_after_send(self, client):
        # the connection dies AFTER the POST went out in full (and not with
        # the idle-close signature): the server may have executed it, so a
        # blind retry would double-create (spurious 409). The transport must
        # surface the connection error instead.
        conn = client.transport._conn()
        attempts = []
        orig_getresponse = conn.getresponse

        def boom():
            attempts.append(1)
            # drain the real response first so the server has definitely
            # executed the create; the failure models the RESPONSE being
            # lost in transit, the truly ambiguous case
            orig_getresponse().read()
            raise ConnectionResetError("connection died awaiting response")

        conn.getresponse = boom
        with pytest.raises(ConnectionResetError):
            client.pods().create(make_pod("np-1"))
        assert len(attempts) == 1
        # the one send really did execute server-side
        assert client.pods().get("np-1").metadata.name == "np-1"

    def test_conn_probe_evicts_peer_closed_connection(self, client):
        # a server idle-close must be caught BEFORE the next request is sent
        # (the readability probe in _conn, emulating Go's background read
        # loop) — otherwise a POST would die after the send, where no safe
        # retry exists. Swap the kept-alive socket for one whose peer has
        # closed and check the transport silently reconnects, even for a
        # non-idempotent create.
        import socket as socketlib
        client.pods().list()                      # establish a kept-alive conn
        conn = client.transport._conn()
        ours, theirs = socketlib.socketpair()
        conn.sock.close()
        conn.sock = ours
        theirs.close()                            # peer closed: EOF pending
        created = client.pods().create(make_pod("idle-evict"))
        assert created.metadata.name == "idle-evict"
        assert client.transport._conn() is not conn

    def test_transport_reuses_one_connection_per_thread(self, client):
        c1 = client.transport._conn()
        client.pods().list()
        assert client.transport._conn() is c1

    def test_single_object_watch_scoped_by_name(self, client):
        client.pods().create(make_pod("target"))
        w = client.transport.request("watch", "pods", namespace="default",
                                     name="other")
        try:
            client.pods().create(make_pod("other"))
            ev = w.next_event(timeout=5)
            assert ev.object.metadata.name == "other"
        finally:
            w.stop()

    def test_status_error_shape(self, server):
        # raw HTTP: 404 carries an encoded api.Status (ref: resthandler.go)
        url = server.base_url + "/api/v1/namespaces/default/pods/nope"
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(url)
        body = json.loads(ei.value.read())
        assert body["kind"] == "Status" and body["code"] == 404


class TestWatchStreaming:
    def test_watch_sees_create_and_delete(self, client):
        w = client.pods().watch()
        try:
            client.pods().create(make_pod("w1"))
            ev = w.next_event(timeout=5)
            assert ev.type == watchpkg.ADDED
            assert ev.object.metadata.name == "w1"
            client.pods().delete("w1")
            types = [w.next_event(timeout=5).type]
            if types[-1] == watchpkg.MODIFIED:  # graceful-delete intermediate
                types.append(w.next_event(timeout=5).type)
            assert types[-1] == watchpkg.DELETED
        finally:
            w.stop()

    def test_watch_frames_born_complete_selflink(self, client, server):
        """The shared-read contract (storage/helper.py): decoded objects
        are decorated at decode-cache insertion, so a watch frame carries
        selfLink REGARDLESS of whether any list/get ran first — wire
        output must never be order-dependent on other channels."""
        w = client.pods().watch()
        try:
            # no list/get has touched this pod before its watch event
            client.pods().create(make_pod("fresh"))
            ev = w.next_event(timeout=5)
            assert ev.type == watchpkg.ADDED
            assert ev.object.metadata.self_link == \
                "/api/v1/namespaces/default/pods/fresh"
        finally:
            w.stop()
        # and a list sees the same selfLink, not a different stamping
        item = [p for p in client.pods().list().items
                if p.metadata.name == "fresh"][0]
        assert item.metadata.self_link == \
            "/api/v1/namespaces/default/pods/fresh"

    def test_watch_from_resource_version(self, client):
        client.pods().create(make_pod("rv1"))
        lst = client.pods().list()
        w = client.pods().watch(resource_version=lst.metadata.resource_version)
        try:
            client.pods().create(make_pod("rv2"))
            ev = w.next_event(timeout=5)
            assert ev.object.metadata.name == "rv2"
        finally:
            w.stop()


class TestUnversionedEndpoints:
    def read(self, server, path):
        with urllib.request.urlopen(server.base_url + path) as r:
            return r.status, r.read().decode()

    def test_healthz_version_validate_index(self, server):
        # deep health: componentstatus-style verdicts for the store and
        # the watch hub (the probe result vocabulary), 200 when healthy;
        # /healthz/ping stays the unconditional liveness answer
        code, body = self.read(server, "/healthz")
        health = json.loads(body)
        assert code == 200 and health["healthy"] is True
        comps = {c["name"]: c["status"] for c in health["items"]}
        assert comps["store"] == "success"
        assert comps["watch-hub"] == "success"
        assert self.read(server, "/healthz/ping")[1] == "ok"
        code, body = self.read(server, "/version")
        assert json.loads(body)["gitVersion"].startswith("v")
        code, body = self.read(server, "/validate")
        assert json.loads(body)["store"]["healthy"] is True
        assert "/api" in json.loads(self.read(server, "/")[1])["paths"]
        assert "v1" in json.loads(self.read(server, "/api")[1])["versions"]

    def test_metrics_exposition(self, server, client):
        client.pods().list()
        code, body = self.read(server, "/metrics")
        assert "# TYPE apiserver_request_count counter" in body
        assert 'verb="get"' in body and 'resource="pods"' in body
        assert "apiserver_request_latencies_seconds_bucket" in body

    def test_v1beta1_flat_encoding(self, server):
        c = Client(HTTPTransport(server.base_url, version="v1beta1"))
        c.pods().create(make_pod("beta"))
        url = server.base_url + "/api/v1beta1/pods?namespace=default"
        wire = json.loads(urllib.request.urlopen(url).read())
        assert wire["apiVersion"] == "v1beta1"
        assert wire["items"][0]["id"] == "beta"  # name spelled id, flattened
        assert "metadata" not in wire["items"][0]
        # and the same object is visible under v1 nested form
        got = Client(HTTPTransport(server.base_url)).pods().get("beta")
        assert got.metadata.name == "beta"


class TestHeaderParsing:
    """RFC 7230 semantics of the fast request parser: repeated fields
    join with ", " (§3.2.2), conflicting Content-Length repeats are
    rejected (§3.3.2), Connection is matched as a token list."""

    def raw(self, server, request: bytes) -> bytes:
        import socket as socketlib
        s = socketlib.create_connection(("127.0.0.1", server.port), timeout=5)
        try:
            s.sendall(request)
            s.shutdown(socketlib.SHUT_WR)
            chunks = []
            while True:
                b = s.recv(65536)
                if not b:
                    break
                chunks.append(b)
            return b"".join(chunks)
        finally:
            s.close()

    def parse(self, raw: bytes):
        """Drive _Handler.parse_request over in-memory pipes; returns the
        parsed handler (inspect .headers) — or the error response bytes."""
        import io
        from kubernetes_tpu.apiserver.http import _Handler
        h = object.__new__(_Handler)
        h.rfile = io.BytesIO(raw)
        h.wfile = io.BytesIO()
        h.client_address = ("127.0.0.1", 0)
        h.server = None
        h.requestline = ""
        h.raw_requestline = h.rfile.readline()
        ok = h.parse_request()
        return h if ok else h.wfile.getvalue()

    def test_repeated_headers_join(self, server):
        # two X-Forwarded-For lines must BOTH survive, joined per §3.2.2
        # (a last-wins parser would drop the first)
        h = self.parse(b"GET / HTTP/1.1\r\nHost: h\r\n"
                       b"X-Forwarded-For: 1.1.1.1\r\n"
                       b"X-Forwarded-For: 2.2.2.2\r\n\r\n")
        assert h.headers.get("X-Forwarded-For") == "1.1.1.1, 2.2.2.2"
        # and the live server still serves such a request
        resp = self.raw(server,
                        b"GET / HTTP/1.1\r\nHost: h\r\n"
                        b"X-Forwarded-For: 1.1.1.1\r\n"
                        b"X-Forwarded-For: 2.2.2.2\r\n"
                        b"Connection: close\r\n\r\n")
        assert resp.startswith(b"HTTP/1.1 200")

    def test_expect_tokens_no_space(self, server):
        # "100-continue,ext" (no space after comma) must still trigger
        # the 100 Continue path; parse alone proves token recognition
        h = self.parse(b"POST /x HTTP/1.0\r\nHost: h\r\n"
                       b"Expect: 100-continue,ext\r\n\r\n")
        # HTTP/1.0 request: no 100-continue sent, but parse must succeed
        assert h.headers.get("Expect") == "100-continue,ext"

    def test_chunked_transfer_encoding_501(self, server):
        resp = self.raw(server,
                        b"POST /api/v1/namespaces/default/pods HTTP/1.1\r\n"
                        b"Host: h\r\nTransfer-Encoding: chunked\r\n\r\n"
                        b"5\r\nhello\r\n0\r\n\r\n")
        assert resp.startswith(b"HTTP/1.1 501")

    def test_conflicting_content_length_400(self, server):
        resp = self.raw(server,
                        b"POST /api/v1/namespaces/default/pods HTTP/1.1\r\n"
                        b"Host: h\r\nContent-Length: 2\r\n"
                        b"Content-Length: 5\r\nConnection: close\r\n\r\n{}abc")
        assert resp.startswith(b"HTTP/1.1 400")

    def test_identical_content_length_repeat_ok(self, server):
        resp = self.raw(server,
                        b"GET /healthz HTTP/1.1\r\nHost: h\r\n"
                        b"Content-Length: 0\r\nContent-Length: 0\r\n"
                        b"Connection: close\r\n\r\n")
        assert resp.startswith(b"HTTP/1.1 200")

    def test_connection_close_among_tokens(self, server):
        # "keep-alive, close" must be honored as close: the server must
        # finish the response and EOF rather than hold the socket open
        resp = self.raw(server,
                        b"GET /healthz/ping HTTP/1.1\r\nHost: h\r\n"
                        b"Connection: keep-alive, close\r\n\r\n")
        assert resp.startswith(b"HTTP/1.1 200") and resp.endswith(b"ok")

    def test_many_repeated_headers_431(self, server):
        lines = b"".join(b"X-A: spam\r\n" for _ in range(250))
        resp = self.raw(server,
                        b"GET /healthz HTTP/1.1\r\nHost: h\r\n" + lines +
                        b"\r\n")
        assert resp.startswith(b"HTTP/1.1 431")


class TestAuth:
    def make_server(self, authorizer=None, authenticator=None):
        m = Master(MasterConfig(authorizer=authorizer))
        return APIServer(m, authenticator=authenticator).start()

    def test_authenticators(self):
        tok = load_token_file("tok1,alice,uid1\ntok2,bob,uid2\n")
        pw = BasicAuthAuthenticator(load_password_file("pw,carol,uid3\n"))
        union = UnionAuthenticator(tok, pw)
        info, ok = union.authenticate(AuthRequest(
            headers={"Authorization": "Bearer tok2"}))
        assert ok and info.name == "bob"
        import base64
        creds = base64.b64encode(b"carol:pw").decode()
        info, ok = union.authenticate(AuthRequest(
            headers={"Authorization": f"Basic {creds}"}))
        assert ok and info.name == "carol"
        assert union.authenticate(AuthRequest(headers={}))[1] is False

    def test_401_then_ok(self):
        srv = self.make_server(
            authenticator=TokenAuthenticator({"sekrit": UserInfo(name="alice")}))
        try:
            with pytest.raises(errors.StatusError) as ei:
                Client(HTTPTransport(srv.base_url)).pods().list()
            assert ei.value.code == 401
            out = Client(HTTPTransport(
                srv.base_url, auth=("bearer", "sekrit"))).pods().list()
            assert out.items == []
        finally:
            srv.stop()

    def test_abac_readonly_matrix(self):
        # alice: full access; bob: readonly (ref: abac example_policy_file.jsonl)
        authz = ABACAuthorizer.from_text(
            '{"user": "alice"}\n{"user": "bob", "readonly": true}\n')
        srv = self.make_server(
            authorizer=authz,
            authenticator=TokenAuthenticator({
                "a": UserInfo(name="alice"), "b": UserInfo(name="bob")}))
        try:
            alice = Client(HTTPTransport(srv.base_url, auth=("bearer", "a")))
            bob = Client(HTTPTransport(srv.base_url, auth=("bearer", "b")))
            alice.pods().create(make_pod("ok"))
            assert [p.metadata.name for p in bob.pods().list().items] == ["ok"]
            with pytest.raises(errors.StatusError) as ei:
                bob.pods().create(make_pod("denied"))
            assert ei.value.code == 403
        finally:
            srv.stop()


# -- CORS (ref: pkg/apiserver/handlers.go CORS + --cors_allowed_origins) ----

class TestCORS:
    @pytest.fixture()
    def cors_server(self):
        srv = APIServer(Master(MasterConfig()),
                        cors_allowed_origins=[r"http://localhost(:\d+)?",
                                              r"https?://.*\.example\.com"]).start()
        yield srv
        srv.stop()

    def _get(self, srv, path, origin=None, method="GET"):
        req = urllib.request.Request(srv.base_url + path, method=method)
        if origin:
            req.add_header("Origin", origin)
        return urllib.request.urlopen(req, timeout=5)

    def test_allowed_origin_gets_cors_headers(self, cors_server):
        r = self._get(cors_server, "/api/v1/namespaces/default/pods",
                      origin="http://localhost:3000")
        assert r.headers["Access-Control-Allow-Origin"] == "http://localhost:3000"
        assert "GET" in r.headers["Access-Control-Allow-Methods"]
        assert r.headers["Access-Control-Allow-Credentials"] == "true"

    def test_regex_subdomain_match(self, cors_server):
        r = self._get(cors_server, "/healthz",
                      origin="https://ui.example.com")
        assert r.headers["Access-Control-Allow-Origin"] == "https://ui.example.com"

    def test_disallowed_origin_gets_no_cors_headers(self, cors_server):
        r = self._get(cors_server, "/healthz", origin="http://evil.test")
        assert r.headers.get("Access-Control-Allow-Origin") is None

    def test_lookalike_origin_rejected(self, cors_server):
        # anchored fullmatch: a pattern admitting *.example.com must NOT
        # grant credentialed CORS to example.com.evil.net-style lookalikes
        for origin in ("https://ui.example.com.evil.net",
                       "http://localhost:3000.evil.net",
                       "evil-https://ui.example.com"):
            r = self._get(cors_server, "/healthz", origin=origin)
            assert r.headers.get("Access-Control-Allow-Origin") is None, origin

    def test_no_origin_header_gets_no_cors_headers(self, cors_server):
        r = self._get(cors_server, "/healthz")
        assert r.headers.get("Access-Control-Allow-Origin") is None

    def test_preflight_options_short_circuits(self, cors_server):
        r = self._get(cors_server, "/api/v1/namespaces/default/pods",
                      origin="http://localhost:8000", method="OPTIONS")
        assert r.status == 204
        assert r.headers["Access-Control-Allow-Origin"] == "http://localhost:8000"
        assert "OPTIONS" in r.headers["Access-Control-Allow-Methods"]

    def test_cors_disabled_by_default(self, server):
        # the plain fixture has no allow-list: even a localhost origin
        # gets nothing (handlers.go: empty list = CORS off)
        req = urllib.request.Request(
            server.base_url + "/healthz")
        req.add_header("Origin", "http://localhost:3000")
        r = urllib.request.urlopen(req, timeout=5)
        assert r.headers.get("Access-Control-Allow-Origin") is None

    def test_vary_origin_when_cors_enabled(self, cors_server):
        # present on matches AND non-matches: the response varies by
        # Origin either way, so caches must key on it
        r = self._get(cors_server, "/healthz", origin="http://localhost:1")
        assert "Origin" in (r.headers.get("Vary") or "")
        r2 = self._get(cors_server, "/healthz", origin="http://evil.test")
        assert "Origin" in (r2.headers.get("Vary") or "")

    def test_options_stays_501_when_not_preflight(self, cors_server, server):
        import urllib.error
        for srv, origin in ((cors_server, "http://evil.test"),
                            (server, "http://localhost:3000")):
            req = urllib.request.Request(
                srv.base_url + "/api/v1/namespaces/default/pods",
                method="OPTIONS")
            req.add_header("Origin", origin)
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=5)
            assert ei.value.code == 501  # the pre-CORS behavior, preserved


# -- read-only port + rate limit (ref: handlers.go ReadOnly/RateLimit) ------

class TestReadOnlyAndRateLimit:
    def test_read_only_serves_get_rejects_writes(self):
        import urllib.error
        srv = APIServer(Master(MasterConfig()), read_only=True).start()
        try:
            r = urllib.request.urlopen(
                srv.base_url + "/api/v1/namespaces/default/pods", timeout=5)
            assert r.status == 200
            req = urllib.request.Request(
                srv.base_url + "/api/v1/namespaces/default/pods",
                data=b"{}", headers={"Content-Type": "application/json"},
                method="POST")
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=5)
            assert ei.value.code == 403
            assert "read-only" in ei.value.read().decode()
        finally:
            srv.stop()

    def test_rate_limit_429_with_retry_after(self):
        from kubernetes_tpu.util.throttle import TokenBucketRateLimiter
        # tiny bucket: 2 requests then dry (qps so low it can't refill)
        rl = TokenBucketRateLimiter(qps=0.001, burst=2)
        import urllib.error
        srv = APIServer(Master(MasterConfig()), read_only=True,
                        rate_limiter=rl).start()
        try:
            for _ in range(2):
                assert urllib.request.urlopen(
                    srv.base_url + "/healthz", timeout=5).status == 200
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(srv.base_url + "/healthz", timeout=5)
            assert ei.value.code == 429
            # kube-fairshed: the hint is MEASURED from the bucket's
            # refill math (clamped 1-30), no longer the constant "1" —
            # and the same number rides the Status details
            hdr = int(ei.value.headers["Retry-After"])
            assert 1 <= hdr <= 30
            body = json.loads(ei.value.read())
            # one Status-encoding path for every error (scheme-encoded)
            assert body["reason"] == "TooManyRequests", body
            assert body["details"]["retryAfterSeconds"] == hdr
        finally:
            srv.stop()

    def test_rejected_write_consumes_no_token(self):
        # ReadOnly(RateLimit(handler)) ordering: the GET-only gate runs
        # BEFORE the limiter, so a rejected write can't starve reads
        from kubernetes_tpu.util.throttle import TokenBucketRateLimiter
        import urllib.error
        rl = TokenBucketRateLimiter(qps=0.001, burst=2)
        srv = APIServer(Master(MasterConfig()), read_only=True,
                        rate_limiter=rl).start()
        try:
            for _ in range(5):
                req = urllib.request.Request(
                    srv.base_url + "/api/v1/namespaces/default/pods",
                    data=b"{}", headers={"Content-Type": "application/json"},
                    method="POST")
                with pytest.raises(urllib.error.HTTPError) as ei:
                    urllib.request.urlopen(req, timeout=5)
                assert ei.value.code == 403
            # both tokens still available for the reads
            for _ in range(2):
                assert urllib.request.urlopen(
                    srv.base_url + "/healthz", timeout=5).status == 200
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(srv.base_url + "/healthz", timeout=5)
            assert ei.value.code == 429
        finally:
            srv.stop()

    def test_read_only_port_preflights_work_and_never_eat_tokens(self):
        """The read-only throttled port must keep serving allowed-origin
        preflights (non-simple GETs — Authorization etc. — need them)
        while neither preflights nor non-CORS OPTIONS may consume the
        tokens legitimate reads need."""
        from kubernetes_tpu.util.throttle import TokenBucketRateLimiter
        import urllib.error
        rl = TokenBucketRateLimiter(qps=0.001, burst=2)
        srv = APIServer(Master(MasterConfig()), read_only=True,
                        rate_limiter=rl,
                        cors_allowed_origins=[r"http://localhost(:\d+)?"],
                        ).start()
        try:
            # allowed-origin preflights: 204 + CORS headers, token-free
            for _ in range(5):
                req = urllib.request.Request(
                    srv.base_url + "/api/v1/namespaces/default/pods",
                    method="OPTIONS")
                req.add_header("Origin", "http://localhost:3000")
                r = urllib.request.urlopen(req, timeout=5)
                assert r.status == 204
                assert r.headers["Access-Control-Allow-Origin"] == \
                    "http://localhost:3000"
            # non-preflight OPTIONS: the ReadOnly gate rejects it BEFORE
            # the limiter (no token consumed)
            for _ in range(5):
                req = urllib.request.Request(
                    srv.base_url + "/api/v1/namespaces/default/pods",
                    method="OPTIONS")
                req.add_header("Origin", "http://evil.test")
                with pytest.raises(urllib.error.HTTPError) as ei:
                    urllib.request.urlopen(req, timeout=5)
                assert ei.value.code == 403
            # both tokens still available for the reads
            for _ in range(2):
                assert urllib.request.urlopen(
                    srv.base_url + "/healthz", timeout=5).status == 200
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(srv.base_url + "/healthz", timeout=5)
            assert ei.value.code == 429
        finally:
            srv.stop()

    def test_token_bucket_refills_at_qps(self):
        from kubernetes_tpu.util.throttle import TokenBucketRateLimiter
        now = [0.0]
        rl = TokenBucketRateLimiter(qps=2.0, burst=3, clock=lambda: now[0])
        assert [rl.can_accept() for _ in range(4)] == [True, True, True, False]
        now[0] = 1.0          # 2 tokens refilled at 2 qps
        assert rl.can_accept() and rl.can_accept() and not rl.can_accept()
        now[0] = 100.0        # capped at burst, never beyond
        assert [rl.can_accept() for _ in range(4)] == [True, True, True, False]


# -- encode-once watch fan-out + batched bind (docs/design/apiserver-hotpath.md)


class _RawWatch:
    """A raw-socket chunked watch client: reads the EXACT bytes the server
    writes (one chunk per frame), so byte-identity across watchers is
    checkable without a JSON layer in between."""

    def __init__(self, port, path="/api/v1/pods?watch=1", connect_only=False):
        import socket as socketlib

        self.sock = socketlib.create_connection(("127.0.0.1", port))
        self.sock.sendall(
            f"GET {path} HTTP/1.1\r\nHost: t\r\n\r\n".encode())
        self.f = self.sock.makefile("rb")
        if not connect_only:
            self.read_headers()

    def read_headers(self):
        while True:
            line = self.f.readline()
            if line in (b"\r\n", b""):
                return

    def read_frame(self, timeout=5.0):
        """One chunk payload (one watch frame) or None at end-of-stream."""
        self.sock.settimeout(timeout)
        size_line = self.f.readline()
        if not size_line:
            return None
        n = int(size_line.strip(), 16)
        if n == 0:
            self.f.readline()
            return None
        data = self.f.read(n)
        self.f.readline()  # trailing CRLF
        return data

    def close(self):
        try:
            self.sock.close()
        except OSError:
            pass


class TestWatchFanout:
    def test_n_watchers_identical_byte_frames_in_order(self, client, server):
        watchers = [_RawWatch(server.port) for _ in range(4)]
        try:
            client.pods().create(make_pod("fo-a"))
            client.pods().create(make_pod("fo-b"))
            got = client.pods().get("fo-a")
            got.metadata.labels = {"round": "two"}
            client.pods().update(got)
            client.pods().delete("fo-b")
            streams = [[w.read_frame() for _ in range(4)] for w in watchers]
        finally:
            for w in watchers:
                w.close()
        # every watcher saw the SAME bytes in the SAME order
        for other in streams[1:]:
            assert other == streams[0]
        frames = [json.loads(f) for f in streams[0]]
        types = [f["type"] for f in frames]
        assert types[:3] == ["ADDED", "ADDED", "MODIFIED"]
        assert types[3] in ("MODIFIED", "DELETED")  # graceful-delete shape
        names = [f["object"]["metadata"]["name"] for f in frames]
        assert names == ["fo-a", "fo-b", "fo-a", "fo-b"]
        # the fan-out encoded each revision at most once: with 4 watchers,
        # at least 3 of every 4 deliveries came from cached bytes
        hits = server.metric_frame_hits.total()
        misses = server.metric_frame_misses.total()
        assert hits >= 3 * max(misses, 1)

    def test_slow_watcher_drops_to_resync_fast_watcher_unaffected(self):
        from kubernetes_tpu.util import chaos
        from kubernetes_tpu.util import metrics as metrics_pkg

        srv = APIServer(Master(MasterConfig()), watch_lag_limit=8).start()
        client = Client(HTTPTransport(srv.base_url))
        resyncs0 = metrics_pkg.default_registry().counter(
            "watch_lag_resyncs_total").total()
        slow = fast = None
        try:
            # the "slow" watcher is deterministically slow: its writer
            # parks on a chaos gate before draining, so its producer-side
            # queue grows on exact depth instead of kernel-buffer luck
            chaos.inject_gate("apiserver.watch.write.lagger")
            slow = _RawWatch(
                srv.port, path="/api/v1/pods?watch=1&chaosGate=lagger")
            fast = _RawWatch(srv.port)
            # distinct keys -> uncoalescible ADDEDs: once the slow
            # watcher's queue passes the bound, it must drop to resync
            # instead of queueing without bound. Watcher.send runs
            # synchronously in the create path, so by the time these
            # requests return the resync has already been counted.
            # The fast watcher is held to "fast" by the test itself: each
            # create's frame is read before the next create is sent, so
            # its queue never holds more than one event however slowly a
            # loaded host runs its writer thread (40 creates back to back
            # can overrun a bound of 8 on ANY watcher — that is the bound
            # working, not a fault).
            fast_frames = []
            for i in range(40):
                client.pods().create(make_pod(f"lag-{i:03d}"))
                fast_frames.append(fast.read_frame(timeout=30))
            # exactly one watcher was dropped: the parked one
            assert metrics_pkg.default_registry().counter(
                "watch_lag_resyncs_total").total() == resyncs0 + 1
            # fast watcher: lossless, streaming the whole time
            names = [json.loads(f)["object"]["metadata"]["name"]
                     for f in fast_frames]
            assert names == [f"lag-{i:03d}" for i in range(40)]
            # open the gate: the slow writer wakes, finds the cleared
            # queue, and delivers exactly ERROR + end-of-stream
            chaos.release_gate("apiserver.watch.write.lagger")
            frames = []
            while True:
                f = slow.read_frame(timeout=10)
                if f is None:
                    break
                frames.append(f)
            last = json.loads(frames[-1])
            assert last["type"] == "ERROR"
            assert last["object"]["code"] == 410
            assert last["object"]["reason"] == "Expired"
            assert srv.metric_watch_lag_drops.total() == 1
            # the 410 ended the stream cleanly -> a client re-lists and
            # re-watches (the Reflector contract) and sees current state
            assert len(client.pods().list().items) == 40
        finally:
            chaos.clear()
            if slow is not None:
                slow.close()
            if fast is not None:
                fast.close()
            srv.stop()

    def test_slow_watcher_coalesces_same_key_modifies(self):
        from kubernetes_tpu.util import chaos
        from kubernetes_tpu.util import metrics as metrics_pkg

        srv = APIServer(Master(MasterConfig()), watch_lag_limit=8).start()
        client = Client(HTTPTransport(srv.base_url))
        coalesced0 = metrics_pkg.default_registry().counter(
            "watch_events_coalesced_total").total()
        slow = None
        try:
            # park the writer on a chaos gate: the queue fills to the lag
            # bound deterministically, then same-key MODIFYs coalesce
            chaos.inject_gate("apiserver.watch.write.stall")
            slow = _RawWatch(
                srv.port, path="/api/v1/pods?watch=1&chaosGate=stall")
            client.pods().create(make_pod("co-1"))
            last_rv = ""
            for i in range(60):
                got = client.pods().get("co-1")
                got.metadata.labels = {"round": str(i)}
                last_rv = client.pods().update(got).metadata.resource_version
            # one key, modify-chain events: the lagging watcher coalesces
            # instead of resyncing — counted synchronously in the update
            # path, so this is already observable before the gate opens
            assert metrics_pkg.default_registry().counter(
                "watch_events_coalesced_total").total() > coalesced0
            chaos.release_gate("apiserver.watch.write.stall")
            # ...and still converges on the LATEST state
            frames = []
            while True:
                f = slow.read_frame(timeout=10)
                frames.append(json.loads(f))
                if frames[-1]["object"]["metadata"].get(
                        "resourceVersion") == last_rv:
                    break
                assert frames[-1]["type"] != "ERROR", frames[-1]
            assert frames[0]["type"] == "ADDED"
            assert all(f["type"] == "MODIFIED" for f in frames[1:])
            # strictly fewer frames than updates: intermediates were merged
            assert len(frames) < 61
            assert srv.metric_watch_lag_drops.total() == 0
        finally:
            chaos.clear()
            if slow is not None:
                slow.close()
            srv.stop()


def _binding(pod, host, ns="default"):
    return api.Binding(
        metadata=api.ObjectMeta(name=pod, namespace=ns),
        pod_name=pod, host=host)


class TestBatchBind:
    def test_batch_bind_partial_failure_per_item(self, client, server):
        for n in ("bba", "bbb", "bbc"):
            client.pods().create(make_pod(n))
        client.pods().bind(_binding("bbb", "m-pre"))  # per-pod path
        res = client.pods().bind_many(api.BindingList(items=[
            _binding("bba", "m1"),
            _binding("bbb", "m2"),        # CAS conflict: already assigned
            _binding("ghost", "m3"),      # not found
            _binding("bbc", ""),          # invalid: no host
            _binding("bbc", "m4"),
        ]))
        assert isinstance(res, api.BindingResultList)
        codes = [r.code for r in res.items]
        errs = [bool(r.error) for r in res.items]
        assert errs == [False, True, True, True, False]
        assert codes[1] == 409 and codes[2] == 404 and codes[3] == 400
        assert client.pods().get("bba").spec.host == "m1"
        assert client.pods().get("bbb").spec.host == "m-pre"  # CAS held
        assert client.pods().get("bbc").spec.host == "m4"
        # one keep-alive request carried the whole wave
        assert server.metric_batch_bind_size.count() == 1
        assert ("post", "bindings:batch") in {
            (k[0], k[1]) for k in server.metric_requests.by_label()}

    def test_batch_bind_bit_identical_to_per_pod_binds(self):
        """The same wave committed per-pod and batched must produce the
        SAME per-item outcomes and the SAME final cluster state — the
        batch endpoint changes the wire shape, never CAS semantics."""
        wave = [("p0", "h1"), ("p1", "h2"), ("p0", "h3"),  # dup: CAS loser
                ("nope", "h1"), ("p2", "h1")]

        def outcomes_per_pod():
            srv = APIServer(Master(MasterConfig())).start()
            c = Client(HTTPTransport(srv.base_url))
            try:
                for n in ("p0", "p1", "p2"):
                    c.pods().create(make_pod(n))
                out = []
                for pod, host in wave:
                    try:
                        c.pods().bind(_binding(pod, host))
                        out.append(0)
                    except errors.StatusError as e:
                        out.append(e.code)
                hosts = {p.metadata.name: p.spec.host
                         for p in c.pods().list().items}
                return out, hosts
            finally:
                srv.stop()

        def outcomes_batch():
            srv = APIServer(Master(MasterConfig())).start()
            c = Client(HTTPTransport(srv.base_url))
            try:
                for n in ("p0", "p1", "p2"):
                    c.pods().create(make_pod(n))
                res = c.pods().bind_many(api.BindingList(
                    items=[_binding(p, h) for p, h in wave]))
                hosts = {p.metadata.name: p.spec.host
                         for p in c.pods().list().items}
                return [r.code for r in res.items], hosts
            finally:
                srv.stop()

        per_pod, hosts_a = outcomes_per_pod()
        batch, hosts_b = outcomes_batch()
        assert per_pod == batch
        assert hosts_a == hosts_b

    def test_batch_bind_requires_binding_list(self, server):
        url = server.base_url + "/api/v1/namespaces/default/bindings:batch"
        req = urllib.request.Request(
            url, data=json.dumps({"kind": "Pod", "apiVersion": "v1",
                                  "metadata": {"name": "x"}}).encode(),
            method="POST")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req)
        assert ei.value.code == 400

    def test_batch_bind_get_is_405(self, server):
        url = server.base_url + "/api/v1/namespaces/default/bindings:batch"
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(url)
        assert ei.value.code == 405

    def test_undecodable_store_payload_surfaces_as_error_frame(self, client,
                                                               server):
        w = _RawWatch(server.port)
        try:
            # bypass the registry: write garbage where pods live, as a
            # corrupt store entry would (the fast translate path defers
            # decode — the failure must still arrive as type ERROR)
            server.master.store.set("/registry/pods/default/bad", "{not json")
            frame = json.loads(w.read_frame())
            assert frame["type"] == "ERROR"
            assert frame["object"]["kind"] == "Status"
            # and the stream keeps going afterwards
            client.pods().create(make_pod("after-bad"))
            nxt = json.loads(w.read_frame())
            assert nxt["type"] == "ADDED"
            assert nxt["object"]["metadata"]["name"] == "after-bad"
        finally:
            w.close()
