"""Reflector/FIFO/Store cache tests (ref: pkg/client/cache/*_test.go)."""

import os
import threading
import time

import pytest

from kubernetes_tpu.api import types as api
from kubernetes_tpu.api.latest import scheme
from kubernetes_tpu.client.cache import (
    FIFO,
    ListWatch,
    Reflector,
    Store,
    StorePodLister,
    StoreServiceLister,
)
from kubernetes_tpu.api.labels import parse_selector
from kubernetes_tpu.storage.helper import StoreHelper
from kubernetes_tpu.storage.memstore import MemStore


def _pod(name, ns="default", labels=None, host=""):
    return api.Pod(metadata=api.ObjectMeta(name=name, namespace=ns, labels=labels or {}),
                   spec=api.PodSpec(host=host))


def test_store_basics():
    s = Store()
    s.add(_pod("a"))
    s.add(_pod("b"))
    assert len(s) == 2
    assert s.get_by_key("default/a").metadata.name == "a"
    s.delete(_pod("a"))
    assert s.get_by_key("default/a") is None
    s.replace([_pod("x")])
    assert s.list_keys() == ["default/x"]


def test_fifo_coalesces_updates():
    f = FIFO()
    p1 = _pod("a")
    f.add(p1)
    p1b = _pod("a")
    p1b.spec.host = "updated"
    f.add(p1b)  # same key: coalesce, keep position
    f.add(_pod("b"))
    first = f.pop()
    assert first.metadata.name == "a" and first.spec.host == "updated"
    assert f.pop().metadata.name == "b"


def test_fifo_pop_blocks_until_add():
    f = FIFO()
    got = []

    def consumer():
        got.append(f.pop())

    t = threading.Thread(target=consumer)
    t.start()
    time.sleep(0.05)
    assert not got
    f.add(_pod("late"))
    t.join(timeout=1)
    assert got and got[0].metadata.name == "late"


def test_fifo_pop_timeout():
    f = FIFO()
    with pytest.raises(TimeoutError):
        f.pop(timeout=0.05)


def test_fifo_delete_skipped_by_pop():
    f = FIFO()
    f.add(_pod("a"))
    f.add(_pod("b"))
    f.delete(_pod("a"))
    assert f.pop().metadata.name == "b"


def _cluster_source():
    """A StoreHelper-backed pods ListWatch, as the real client will provide."""
    h = StoreHelper(MemStore(), scheme)

    def list_fn():
        return h.extract_to_list("/pods", api.PodList)

    def watch_fn(rv):
        return h.watch("/pods", resource_version=rv)

    return h, ListWatch(list_fn, watch_fn)


def _wait_for(pred, timeout=2.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.01)
    return False


def test_reflector_mirrors_store():
    h, lw = _cluster_source()
    h.create_obj("/pods/default/pre", _pod("pre"))
    store = Store()
    r = Reflector(lw, store, name="pods").run()
    try:
        assert _wait_for(lambda: store.get_by_key("default/pre") is not None)
        h.create_obj("/pods/default/live", _pod("live"))
        assert _wait_for(lambda: store.get_by_key("default/live") is not None)
        live = store.get_by_key("default/live")
        live2 = scheme.deep_copy(live)
        live2.spec.host = "n1"
        h.set_obj("/pods/default/live", live2)
        assert _wait_for(
            lambda: (store.get_by_key("default/live") or _pod("x")).spec.host == "n1")
        h.delete_obj("/pods/default/pre")
        assert _wait_for(lambda: store.get_by_key("default/pre") is None)
        assert r.last_sync_resource_version != ""
    finally:
        r.stop()


def test_reflector_into_fifo_feeds_consumer():
    """The scheduler's pattern: unassigned pods reflected into a FIFO
    (ref: factory.go:126)."""
    h, lw = _cluster_source()
    fifo = FIFO()
    r = Reflector(lw, fifo, name="unassigned").run()
    try:
        h.create_obj("/pods/default/w1", _pod("w1"))
        # --race mode preempts between nearly every bytecode: delivery is
        # still guaranteed (the reflector watches from the list rv, so
        # there is no lost-event window) but latency balloons; the
        # assertion is about delivery, not speed
        got = fifo.pop(timeout=10 if os.environ.get("KTPU_RACE") else 2)
        assert got.metadata.name == "w1"
    finally:
        r.stop()


def test_reflector_survives_watch_closure():
    h, lw = _cluster_source()
    store = Store()
    real_watch = lw.watch_fn
    watches = []

    def tracking_watch(rv):
        w = real_watch(rv)
        watches.append(w)
        return w

    lw.watch_fn = tracking_watch
    r = Reflector(lw, store, name="pods").run()
    try:
        h.create_obj("/pods/default/a", _pod("a"))
        assert _wait_for(lambda: store.get_by_key("default/a") is not None)
        watches[-1].close()  # server closes stream: reflector must relist+rewatch
        h.create_obj("/pods/default/b", _pod("b"))
        assert _wait_for(lambda: store.get_by_key("default/b") is not None)
    finally:
        r.stop()


@pytest.mark.parametrize("list_fails", [False, True])
def test_reflector_wait_listed(list_fails):
    """wait_listed returns once the first LIST has landed in the store,
    or has failed (the run loop then backs off and tries again)."""
    release = threading.Event()

    def list_fn():
        assert release.wait(5.0)
        if list_fails:
            raise OSError("apiserver away")
        return api.PodList(items=[_pod("a")],
                           metadata=api.ListMeta(resource_version="1"))

    from kubernetes_tpu import watch as watchpkg
    store = Store()
    r = Reflector(ListWatch(list_fn, lambda rv: watchpkg.Watcher()),
                  store).run()
    try:
        assert not r.wait_listed(0.1)      # the LIST has not come back
        release.set()
        assert r.wait_listed(5.0)
        assert len(store) == (0 if list_fails else 1)
    finally:
        r.stop()
        assert r.join(5.0)


def test_pod_and_service_listers():
    pods = Store()
    pods.add(_pod("a", labels={"app": "web"}))
    pods.add(_pod("b", labels={"app": "db"}))
    lister = StorePodLister(pods)
    assert {p.metadata.name for p in lister.list()} == {"a", "b"}
    assert [p.metadata.name for p in lister.list(parse_selector("app=web"))] == ["a"]

    services = Store()
    services.add(api.Service(metadata=api.ObjectMeta(name="web", namespace="default"),
                             spec=api.ServiceSpec(port=80, selector={"app": "web"})))
    services.add(api.Service(metadata=api.ObjectMeta(name="all", namespace="other"),
                             spec=api.ServiceSpec(port=80, selector={"app": "web"})))
    slister = StoreServiceLister(services)
    got = slister.get_pod_services(_pod("a", labels={"app": "web"}))
    assert [s.metadata.name for s in got] == ["web"]  # namespace-scoped


def test_reflector_stop_join_freezes_store():
    """The post-join freeze contract: once stop()+join() returns True the
    run loop has exited, so no event written to the source afterwards can
    ever land in the store (what the stale-wave tests rely on to freeze a
    scheduler's view deterministically)."""
    h, lw = _cluster_source()
    store = Store()
    r = Reflector(lw, store, name="pods").run()
    try:
        h.create_obj("/pods/default/a", _pod("a"))
        assert _wait_for(lambda: store.get_by_key("default/a") is not None)
    finally:
        r.stop()
    assert r.join(5.0), "reflector thread did not exit"
    # join(True) means the thread is DEAD — a write after it can never be
    # applied, no grace sleep needed
    h.create_obj("/pods/default/late", _pod("late"))
    assert store.get_by_key("default/late") is None
    assert store.get_by_key("default/a") is not None
    # join is idempotent and True on a never-started reflector too
    assert r.join(0.1)
    assert Reflector(lw, Store(), name="never-run").join(0.1)
