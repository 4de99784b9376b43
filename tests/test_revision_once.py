"""A written revision is made once (docs/design/apiserver-hotpath.md).

Every ``StoreHelper`` write verb walks its object to the wire dict once and
hands on the three forms of the revision it made: the bytes the store
holds, the decoded object (seeded into the decode cache) and the wire dict
that the response and the watch frame are dumped from. What must not move
is any byte: each form is held here to what ``Scheme.encode`` /
``Scheme.decode`` give on their own, which is what the tree before the
hand-over produced.
"""

import json
import os
import socket
import sys
import threading

import pytest

from kubernetes_tpu.api import errors
from kubernetes_tpu.api import types as api
from kubernetes_tpu.api.latest import scheme
from kubernetes_tpu.api.meta import accessor
from kubernetes_tpu.apiserver.http import APIServer
from kubernetes_tpu.apiserver.master import Master, MasterConfig
from kubernetes_tpu.registry.generic import Context
from kubernetes_tpu.runtime.serialize import from_wire, roundtrip, to_wire
from kubernetes_tpu.util import metrics

GOLDENS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "tests", "goldens")


def _golden(config: str) -> dict:
    with open(os.path.join(GOLDENS, config + ".json")) as f:
        return json.load(f)


def _manifests() -> dict:
    """name -> (store key, manifest JSON): the benchmark's own templates as
    its feeder posts them, and what else set-up and the recorder write."""
    basic, mixed = _golden("sched-basic-5000n"), _golden("sched-mixed-5000n")
    odd = {
        "kind": "Pod", "apiVersion": "v1",
        "metadata": {"name": "odd", "namespace": "default", "uid": "u-odd",
                     "labels": {"tier": "web"},
                     "creationTimestamp": "2026-10-04T06:00:00.3506Z"},
        "spec": {"nodeSelector": {"disk": "ssd"},
                 "containers": [{"name": "c", "image": "img",
                                 "ports": [{"containerPort": 80}],
                                 "resources": {"limits": {
                                     "cpu": "0.1", "memory": "1024Mi",
                                     "example.com/widget": "1000m"}}}]}}
    service = {
        "kind": "Service", "apiVersion": "v1",
        "metadata": {"name": "svc", "namespace": "default"},
        "spec": {"port": 80, "selector": {"tier": "web"},
                 "portalIP": "10.0.0.7"}}
    event = {
        "kind": "Event", "apiVersion": "v1",
        "metadata": {"name": "p.17a", "namespace": "default"},
        "involvedObject": {"kind": "Pod", "namespace": "default",
                           "name": "p", "uid": "u-p"},
        "reason": "Scheduled", "message": "bound to node-00001",
        "source": {"component": "scheduler"},
        "firstTimestamp": "2026-10-04T06:00:00.25Z", "count": 1}
    return {
        "pod-basic": ("/registry/pods/default/p8012d687-0000000",
                      basic["pods"][0]),
        "pod-node-affinity": ("/registry/pods/default/p8012d687-0000000",
                              mixed["pods"]["pod-with-node-affinity"]),
        "node": ("/registry/minions/node-00587", basic["nodes_first"][0]),
        "node-labelled": ("/registry/minions/node-00587",
                          mixed["nodes_first"][0]),
        "service": ("/registry/services/default/svc", json.dumps(service)),
        "event": ("/registry/events/default/p.17a", json.dumps(event)),
        "pod-odd": ("/registry/pods/default/odd", json.dumps(odd)),
    }


MANIFESTS = _manifests()


def _touch(obj):
    """The kind of change a bind or a status update makes."""
    obj.metadata.labels = dict(obj.metadata.labels, touched="yes")
    return obj


# Each verb as ``f(helper, key, obj) -> the written object``; the CAS verbs
# write revision 1 themselves first, the way their callers find one.
def _create(h, key, obj):
    return h.create_obj(key, obj)


def _set(h, key, obj):
    h.create_obj(key, scheme.deep_copy(obj))
    return h.set_obj(key, _touch(obj))


def _atomic(h, key, obj):
    h.create_obj(key, obj)
    return h.atomic_update(key, type(obj), _touch)


def _atomic_many(h, key, obj):
    h.create_obj(key, obj)
    (out,) = h.atomic_update_many(type(obj), [(key, _touch)])
    return out


def _bind_evict(h, key, obj):
    h.create_obj(key, obj)
    (out,) = h.atomic_bind_evict_many(type(obj), [(key, _touch, [])])
    return out


VERBS = {"create_obj": _create, "set_obj": _set, "atomic_update": _atomic,
         "atomic_update_many": _atomic_many,
         "atomic_bind_evict_many": _bind_evict}


_LABEL = {"runtime_codec_passes_total": "direction",
          "storage_decode_cache_total": "source"}


def _cached(series: str, label: str) -> float:
    """One of the two counters the hand-over added, as it stands."""
    return metrics.default_registry().counter(
        series, "", (_LABEL[series],)).value(label)


def _finished(helper, kv):
    """What a reader of the parent's tree got for ``kv``: the codec over
    the stored bytes, the resourceVersion, the linkers."""
    ref = scheme.decode(kv.value)
    accessor.set_resource_version(ref, str(kv.modified_index))
    for prefix, fn in helper._linkers:
        if kv.key.startswith(prefix):
            fn(ref)
    return ref


@pytest.fixture()
def apisrv():
    srv = APIServer(Master(MasterConfig()))  # bound, not serving
    yield srv
    srv._httpd.server_close()


@pytest.mark.parametrize("version", ["v1", "v1beta1"])
@pytest.mark.parametrize("what", sorted(MANIFESTS))
@pytest.mark.parametrize("verb", sorted(VERBS))
def test_store_response_and_frame_bytes_are_the_codecs(apisrv, verb, what,
                                                       version):
    helper, store = apisrv.master.helper, apisrv.master.store
    key, manifest = MANIFESTS[what]
    obj = scheme.decode(manifest)

    seeded0 = _cached("storage_decode_cache_total", "write")
    decoded0 = _cached("storage_decode_cache_total", "codec")
    out = VERBS[verb](helper, key, obj)
    kv = store.get(key)
    assert accessor.resource_version(out) == str(kv.modified_index)

    # the store bytes: the written object less its resourceVersion
    bare = scheme.deep_copy(out)
    accessor.set_resource_version(bare, "")
    assert kv.value == scheme.encode(bare)

    # the decoded object: seeded by the write, equal to a decode, and no
    # reader ran the codec to get it
    ref = _finished(helper, kv)
    decodes = _cached("runtime_codec_passes_total", "decode")
    assert helper._decode(kv) == ref
    assert helper.extract_to_list(key.rsplit("/", 1)[0],
                                  api.PodList).items == [ref]
    assert _cached("runtime_codec_passes_total", "decode") == decodes
    assert _cached("storage_decode_cache_total", "codec") == decoded0
    assert _cached("storage_decode_cache_total", "write") - seeded0 == \
        (1 if verb == "create_obj" else 2)

    # the response, as the master returns the object: selfLink stamped
    resource = {"Pod": "pods", "Node": "nodes", "Service": "services",
                "Event": "events"}[out.kind]
    apisrv.master._stamp_self_links(resource, out)
    want = scheme.encode(out, version)
    encodes = _cached("runtime_codec_passes_total", "encode")
    assert apisrv.encode_response(out, version, written=True) == want
    # ... from the write's own walk in the version the store holds, through
    # the codec in a version that has transforms
    assert _cached("runtime_codec_passes_total", "encode") - encodes == \
        (0 if version == "v1" else 1)

    # the frame a watcher of that revision is sent, in either version
    for v in ("v1", "v1beta1"):
        frame = apisrv.frame_entry("ADDED", helper._decode(kv), v)[0]
        assert frame == '{"type": "ADDED", "object": %s}' % \
            scheme.encode(ref, v)
    assert '"selfLink": "/api/v1/' in want


@pytest.mark.parametrize("what", sorted(MANIFESTS))
def test_on_bound_seeds_the_frame_a_codec_would(apisrv, what):
    """``_handle_batch_bind``'s hook: the bound revision's frame comes from
    the bind's walk, in v1, and is the codec's bytes."""
    helper = apisrv.master.helper
    key, manifest = MANIFESTS[what]
    helper.create_obj(key, scheme.decode(manifest))
    (out,) = helper.atomic_update_many(api.Pod, [(key, _touch)])
    encodes = _cached("runtime_codec_passes_total", "encode")
    apisrv.seed_frame(out, "v1", written=True)
    assert _cached("runtime_codec_passes_total", "encode") == encodes
    kv = apisrv.master.store.get(key)
    assert apisrv._wire_cache[(str(kv.modified_index), "v1")] == \
        scheme.encode(_finished(helper, kv))
    assert helper.take_wire(out) is None  # handed on once


@pytest.mark.parametrize("what", sorted(MANIFESTS))
def test_roundtrip_is_the_codec_without_the_wire(what):
    obj = scheme.decode(MANIFESTS[what][1])
    copy = scheme.deep_copy(obj)
    assert copy == obj == scheme.decode(scheme.encode(obj))
    assert copy is not obj and copy.metadata is not obj.metadata
    assert copy.metadata.labels is not obj.metadata.labels


def test_roundtrip_hands_back_what_a_decode_would_of_a_sloppy_object():
    """An in-process writer's object need not be what the codec makes of
    it; the copy a write seeds is."""
    pod = api.Pod(
        metadata=api.ObjectMeta(name="x", annotations=None, labels=None,
                                creation_timestamp="2026-01-01T00:00:00.35Z"),
        status=None)
    pod.spec.containers = (api.Container(
        name="c", resources=api.ResourceRequirements(limits={"cpu": 2})),)
    pod.spec.restart_policy = ""
    want = from_wire(api.Pod, to_wire(pod))
    assert roundtrip(pod) == want
    assert want.metadata.annotations == {} and want.status is not None
    copy = scheme.deep_copy(pod)
    assert copy == scheme.decode(scheme.encode(pod))
    assert copy.spec.restart_policy == "Always"   # the version's defaulter


def test_a_mutated_result_leaves_the_written_revision_served():
    """The caller owns what a write returns: the cache was seeded a copy."""
    master = Master(MasterConfig())
    helper = master.helper
    key, manifest = MANIFESTS["pod-odd"]
    w = helper.watch("/registry/pods", resource_version="0")
    try:
        out = helper.create_obj(key, scheme.decode(manifest))
        ref = _finished(helper, master.store.get(key))
        out.metadata.labels["tier"] = "mutated"
        out.spec.containers.clear()
        out.spec.node_selector = {}
        assert helper.extract_to_list("/registry/pods",
                                      api.PodList).items == [ref]
        assert master.pods.get(Context(namespace="default"), "odd") == ref
        ev = next(iter(w))
        assert ev.type == "ADDED" and ev.object == ref
    finally:
        w.stop()


def test_a_cas_that_loses_seeds_only_the_revision_that_landed():
    master = Master(MasterConfig())
    helper, store = master.helper, master.store
    key, manifest = MANIFESTS["pod-basic"]
    helper.create_obj(key, scheme.decode(manifest))
    calls = []

    def update(pod):
        if not calls:  # an interloper lands between the read and the CAS
            other = helper.extract_obj(key)
            other.metadata.labels["winner"] = "interloper"
            helper.set_obj(key, other)
        calls.append(pod.metadata.resource_version)
        pod.metadata.labels["attempt"] = str(len(calls))
        return pod

    seeded0 = _cached("storage_decode_cache_total", "write")
    out = helper.atomic_update(key, api.Pod, update)
    assert len(calls) == 2 and out.metadata.labels == {
        "winner": "interloper", "attempt": "2"}
    # the interloper's set and the retry: two revisions landed, two seeded
    assert _cached("storage_decode_cache_total", "write") - seeded0 == 2
    landed = {int(rv) for rv in calls} | {int(out.metadata.resource_version)}
    assert {idx for (k, idx) in helper._decode_cache if k == key} == landed
    assert {rv for rv, wire in helper._written.items()
            if wire["kind"] == "Pod"} <= {str(i) for i in landed}
    kv = store.get(key)
    assert helper._decode(kv) == _finished(helper, kv)
    assert helper._decode(kv).metadata.labels["attempt"] == "2"
    # what the loser walked is nowhere: its wire cannot be taken
    assert helper.take_wire(out)["metadata"]["labels"]["attempt"] == "2"


def test_a_retried_batch_hands_each_slot_its_own_walk():
    master = Master(MasterConfig())
    helper = master.helper
    keys = []
    for i in range(3):
        pod = scheme.decode(MANIFESTS["pod-basic"][1])
        pod.metadata.name = f"p{i}"
        keys.append(f"/registry/pods/default/p{i}")
        helper.create_obj(keys[-1], pod)
    raced = []

    def bind(host, race_key=None):
        def fn(pod):
            if race_key and not raced:
                raced.append(1)
                helper.atomic_update(race_key, api.Pod, _touch)
            pod.spec.host = host
            return pod
        return fn

    outs = helper.atomic_update_many(api.Pod, [
        (keys[0], bind("h0")), (keys[1], bind("h1", race_key=keys[1])),
        (keys[2], bind("h2"))])
    for i, out in enumerate(outs):
        assert out.spec.host == f"h{i}"
        wire = helper.take_wire(out)
        assert scheme.wire_to_json(wire) == scheme.encode(out)
    assert outs[1].metadata.labels == {"touched": "yes"}


def test_lists_and_unwritten_objects_take_nothing():
    master = Master(MasterConfig())
    helper = master.helper
    key, manifest = MANIFESTS["pod-basic"]
    out = helper.create_obj(key, scheme.decode(manifest))
    lst = helper.extract_to_list("/registry/pods", api.PodList)
    # a list's resourceVersion is a store index an object's can equal
    assert lst.metadata.resource_version == out.metadata.resource_version
    assert helper.take_wire(lst) is None
    assert helper.take_wire(api.Status()) is None
    assert helper.take_wire(api.Pod()) is None
    assert helper.take_wire(out) is not None


def test_writers_and_takers_on_many_threads_never_cross():
    """``_written`` is shared by every handler thread: each taker gets the
    walk of its own write or nothing."""
    master = Master(MasterConfig())
    helper = master.helper
    helper._written.clear()   # the master's own default namespace
    bad, done = [], []

    def worker(t):
        for i in range(60):
            pod = scheme.decode(MANIFESTS["pod-basic"][1])
            pod.metadata.name = f"t{t}-{i}"
            key = f"/registry/pods/default/t{t}-{i}"
            out = helper.create_obj(key, pod)
            out2 = helper.atomic_update(key, api.Pod, _touch)
            for o in (out, out2):
                wire = helper.take_wire(o)
                if wire is None or \
                        scheme.wire_to_json(wire) != scheme.encode(o):
                    bad.append((t, i))
        done.append(t)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(t,), daemon=True)
                   for t in range(2 * (os.cpu_count() or 4))]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert len(done) == len(threads) and not bad
    assert not helper._written


# -- through the served path ---------------------------------------------------

class _RawWatch:
    """The exact chunks a watch stream is sent, one frame a chunk."""

    def __init__(self, port, path):
        self.sock = socket.create_connection(("127.0.0.1", port))
        self.sock.sendall(f"GET {path} HTTP/1.1\r\nHost: t\r\n\r\n".encode())
        self.sock.settimeout(10.0)
        self.f = self.sock.makefile("rb")
        while self.f.readline() not in (b"\r\n", b""):
            pass

    def frame(self) -> dict:
        n = int(self.f.readline().strip(), 16)
        data = self.f.read(n)
        self.f.readline()
        return json.loads(data)

    def close(self):
        self.sock.close()


def _post(port, path, body: dict) -> bytes:
    import http.client
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    try:
        conn.request("POST", path, body=json.dumps(body))
        resp = conn.getresponse()
        data = resp.read()
        assert resp.status in (200, 201), (resp.status, data)
        return data
    finally:
        conn.close()


@pytest.mark.parametrize("version", ["v1", "v1beta1"])
def test_three_filtered_watchers_see_one_create_and_one_bind(version):
    """The window's three watchers (the feeder's ``spec.host!=``, the
    scheduler's ``spec.host=`` and ``spec.host!=``) on one pod's two writes:
    ADDED, DELETED carrying the bound state, ADDED — and the server ran the
    decoder over the two request bodies and nothing else."""
    field = {"v1": "spec.host", "v1beta1": "DesiredState.Host"}[version]
    srv = APIServer(Master(MasterConfig())).start()
    pending = bound_a = bound_b = None
    try:
        base = f"/api/{version}/watch/pods?namespace=default&fields={field}"
        pending = _RawWatch(srv.port, base + "%3D")
        bound_a = _RawWatch(srv.port, base + "!%3D")
        bound_b = _RawWatch(srv.port, base + "!%3D")
        pod = scheme.decode(MANIFESTS["pod-odd"][1])
        decodes = _cached("runtime_codec_passes_total", "decode")
        encodes = _cached("runtime_codec_passes_total", "encode")
        body = json.loads(scheme.encode(pod, version))
        binding = json.loads(scheme.encode(api.BindingList(items=[api.Binding(
            metadata=api.ObjectMeta(name="odd", namespace="default"),
            pod_name="odd", host="node-00001")]), version))
        mine = (_cached("runtime_codec_passes_total", "decode") - decodes,
                _cached("runtime_codec_passes_total", "encode") - encodes)
        assert mine == (0, 2)

        created = json.loads(_post(
            srv.port, f"/api/{version}/pods?namespace=default", body))
        added = pending.frame()
        assert added["type"] == "ADDED" and added["object"] == created
        res = json.loads(_post(
            srv.port, f"/api/{version}/bindings:batch?namespace=default",
            binding))
        assert [bool(r.get("error")) for r in res["items"]] == [False]

        gone = pending.frame()
        assert gone["type"] == "DELETED"        # left the filter, new state
        first, second = bound_a.frame(), bound_b.frame()
        assert first == second and first["type"] == "ADDED"
        assert gone["object"] == first["object"]
        # the two request bodies, and nothing a watcher asked for
        assert _cached("runtime_codec_passes_total", "decode") - decodes == 2
        # v1: one walk a write and the BindingResultList; another version
        # walks each response and each frame seed through its transforms
        assert _cached("runtime_codec_passes_total", "encode") - encodes \
            - mine[1] == (3 if version == "v1" else 5)
        kv = srv.master.store.get("/registry/pods/default/odd")
        assert json.dumps(first["object"], sort_keys=True) == scheme.encode(
            _finished(srv.master.helper, kv), version)
        host = first["object"]["spec" if version == "v1" else
                               "desiredState"]["host"]
        assert host == "node-00001"
    finally:
        for w in (pending, bound_a, bound_b):
            if w is not None:
                w.close()
        srv.stop()


def test_an_unchanged_error_for_a_second_create():
    master = Master(MasterConfig())
    key, manifest = MANIFESTS["pod-basic"]
    master.helper.create_obj(key, scheme.decode(manifest))
    seeded = _cached("storage_decode_cache_total", "write")
    with pytest.raises(errors.StatusError) as ei:
        master.helper.create_obj(key, scheme.decode(manifest))
    assert errors.is_already_exists(ei.value)
    assert _cached("storage_decode_cache_total", "write") == seeded
