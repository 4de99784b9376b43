"""The scheduler's node source: a reflector over the nodes' list+watch that
writes through the schedulable filter, and the lister that keeps its sorted
list while the store stands (scheduler/driver.py)."""

import itertools
import time

import pytest

from kubernetes_tpu import watch as watchpkg
from kubernetes_tpu.api import errors
from kubernetes_tpu.api import types as api
from kubernetes_tpu.api.meta import accessor
from kubernetes_tpu.api.quantity import Quantity
from kubernetes_tpu.apiserver.http import APIServer
from kubernetes_tpu.apiserver.master import Master, MasterConfig
from kubernetes_tpu.client.cache import ListWatch, Store
from kubernetes_tpu.client.client import Client, InProcessTransport
from kubernetes_tpu.client.http import HTTPTransport
from kubernetes_tpu.scheduler.driver import (
    ConfigFactory,
    _SchedulableNodes,
    _StoreMinionLister,
    filter_schedulable_nodes,
)
from kubernetes_tpu.scheduler.tpu_batch import BatchScheduler
from kubernetes_tpu.util import metrics


def mk_node(name, unschedulable=False, conditions=()):
    return api.Node(
        metadata=api.ObjectMeta(name=name),
        spec=api.NodeSpec(capacity={"cpu": Quantity("8"),
                                    "memory": Quantity("16Gi")},
                          unschedulable=unschedulable),
        status=api.NodeStatus(conditions=[
            api.NodeCondition(type=t, status=s) for t, s in conditions]))


def mk_pod(name):
    return api.Pod(
        metadata=api.ObjectMeta(name=name, namespace="default"),
        spec=api.PodSpec(containers=[api.Container(
            name="c", image="i",
            resources=api.ResourceRequirements(limits={
                "cpu": Quantity("500m"), "memory": Quantity("512Mi")}))]))


def _wait(pred, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.01)
    return False


def _listed() -> float:
    return metrics.default_registry().counter(
        "scheduler_node_source_objects_total",
        label_names=("via",)).value("list")


def _watched() -> float:
    return metrics.default_registry().counter(
        "scheduler_node_source_objects_total",
        label_names=("via",)).value("watch")


def _relists() -> float:
    return metrics.default_registry().counter(
        "scheduler_node_source_relists_total").value()


def _names(store) -> list:
    return sorted(n.metadata.name for n in store.list())


# -- (a) every kind of node event arrives by the watch, none by a LIST -------

def _create(client):
    client.nodes().create(mk_node("n-new"))


def _cordon(client, on=True):
    node = client.nodes().get("n1")
    node.spec.unschedulable = on
    client.nodes().update(node)


def _not_ready(client):
    node = client.nodes().get("n1")
    node.status.conditions = [api.NodeCondition(
        type=api.NodeReady, status=api.ConditionFalse)]
    client.nodes().update(node)


NODE_EVENTS = {
    # kind: (what the cluster holds at start, the change, node_store after)
    "created": ((), _create, ["n-new", "n0", "n1", "n2"]),
    "cordoned": ((), _cordon, ["n0", "n2"]),
    "uncordoned": (("n1",), lambda c: _cordon(c, on=False),
                   ["n0", "n1", "n2"]),
    "not_ready": ((), _not_ready, ["n0", "n2"]),
    "deleted": ((), lambda c: c.nodes().delete("n1"), ["n0", "n2"]),
}


@pytest.mark.parametrize("kind", sorted(NODE_EVENTS))
def test_node_event_reaches_the_store_by_the_watch(kind):
    cordoned, change, want = NODE_EVENTS[kind]
    srv = APIServer(Master(MasterConfig()), host="127.0.0.1", port=0).start()
    factory = None
    try:
        user = Client(HTTPTransport(srv.base_url))
        for i in range(3):
            user.nodes().create(mk_node(f"n{i}",
                                        unschedulable=f"n{i}" in cordoned))
        listed = _listed()
        factory = ConfigFactory(Client(HTTPTransport(
            srv.base_url, user_agent="kube-scheduler")))
        factory.create()
        # the first LIST has landed when create() returns: nobody waits
        assert _names(factory.node_store) == sorted(
            {"n0", "n1", "n2"} - set(cordoned))
        assert _listed() == listed + 3
        watched, relists = _watched(), _relists()
        change(user)
        assert _wait(lambda: _names(factory.node_store) == want), \
            _names(factory.node_store)
        assert _watched() == watched + 1
        assert _listed() == listed + 3, "the change came by a LIST"
        assert _relists() == relists
    finally:
        if factory is not None:
            factory.stop(join=True)
        srv.stop()


# -- (b) the filtering front against the list filter, every condition --------

_COND = (None, api.ConditionTrue, api.ConditionFalse)
NODE_STATES = [
    (unsched, sched, ready, reachable)
    for unsched in (False, True)
    for sched, ready, reachable in itertools.product(_COND, _COND, _COND)]


def _state_node(name, state):
    unsched, sched, ready, reachable = state
    kinds = (api.NodeSchedulable, api.NodeReady, api.NodeReachable)
    return mk_node(name, unschedulable=unsched, conditions=[
        (k, s) for k, s in zip(kinds, (sched, ready, reachable))
        if s is not None])


def _state_id(state):
    unsched, *conds = state
    return ("cordoned" if unsched else "open") + "-" + "-".join(
        {None: "none", api.ConditionTrue: "true",
         api.ConditionFalse: "false"}[c] for c in conds)


@pytest.mark.parametrize("state", NODE_STATES, ids=_state_id)
def test_front_agrees_with_list_filter(state):
    """replace / add / update / delete of the front leave the store holding
    what filter_schedulable_nodes keeps of the same nodes."""
    node, plain = _state_node("x", state), mk_node("plain")
    kept = [n.metadata.name for n in filter_schedulable_nodes(
        api.NodeList(items=[node, plain])).items]
    passes = "x" in kept
    assert kept == (["x", "plain"] if passes else ["plain"])

    store = Store()
    front = _SchedulableNodes(store)
    front.replace([node, plain])
    assert _names(store) == sorted(kept)

    store = Store()
    front = _SchedulableNodes(store)
    front.add(plain)
    front.add(node)
    assert _names(store) == sorted(kept)

    # a node that passed takes this state by an update, and the other way
    store = Store()
    front = _SchedulableNodes(store)
    front.replace([mk_node("x"), plain])
    front.update(node)
    assert _names(store) == sorted(kept)
    assert (store.get_by_key("x") is node) == passes
    front.update(mk_node("x"))
    assert _names(store) == ["plain", "x"]

    front.delete(node)
    assert _names(store) == ["plain"]


# -- (c) the conditions that relist -------------------------------------------

class _DeafThenLive:
    """A node client whose first watch stream hears nothing of the server
    (the test breaks it by hand); later ones are the server's own."""

    def __init__(self, inner, gone_on_reopen):
        self.inner = inner
        self.gone_on_reopen = gone_on_reopen
        self.deaf = watchpkg.Watcher()
        self.opened = []

    def list_watch(self):
        real = self.inner.list_watch()

        def watch_fn(rv):
            self.opened.append(rv)
            if len(self.opened) == 1:
                return self.deaf
            if self.gone_on_reopen and len(self.opened) == 2:
                raise errors.new_expired("too old resource version")
            return real.watch_fn(rv)

        return ListWatch(real.list_fn, watch_fn)


@pytest.mark.parametrize("fault", ["closed_cold", "error_event",
                                   "gone_on_reopen"])
def test_broken_watch_relists_once(fault):
    m = Master()
    client = Client(InProcessTransport(m))
    for i in range(4):
        client.nodes().create(mk_node(f"n{i}"))
    nodes = _DeafThenLive(client.nodes(), fault == "gone_on_reopen")
    client.nodes = lambda: nodes
    factory = ConfigFactory(client)
    factory.create()
    try:
        assert _names(factory.node_store) == ["n0", "n1", "n2", "n3"]
        listed, relists = _listed(), _relists()
        # what the deaf stream misses
        user = Client(InProcessTransport(m))
        user.nodes().create(mk_node("n-new"))
        user.nodes().delete("n0")
        _cordon(user)
        assert _names(factory.node_store) == ["n0", "n1", "n2", "n3"]
        if fault == "closed_cold":
            nodes.deaf.close()
        elif fault == "error_event":
            nodes.deaf.send(watchpkg.Event(
                watchpkg.ERROR,
                errors.new_expired("too old resource version").status))
        else:
            # a stream that made progress and closed is re-opened at its
            # last version, and that open is refused: 410
            heard = user.nodes().get("n3")
            assert accessor.resource_version(heard)
            nodes.deaf.send(watchpkg.Event(watchpkg.MODIFIED, heard))
            nodes.deaf.close()
        fresh = filter_schedulable_nodes(user.nodes().list())
        want = sorted(n.metadata.name for n in fresh.items)
        assert want == ["n-new", "n2", "n3"]
        assert _wait(lambda: _names(factory.node_store) == want), \
            _names(factory.node_store)
        assert _relists() == relists + 1
        assert _listed() == listed + 4     # n-new, n1 (cordoned), n2, n3
        # and the stream after the relist is live
        user.nodes().create(mk_node("n-late"))
        assert _wait(lambda: "n-late" in _names(factory.node_store))
        assert _relists() == relists + 1
    finally:
        factory.stop(join=True)


# -- (d) the lister keeps its list while the store's token stands -------------

def _add(store):
    store.add(mk_node("a"))


def _update(store):
    store.update(mk_node("m"))


def _delete(store):
    store.delete(mk_node("m"))


def _replace(store):
    store.replace([mk_node("q"), mk_node("c")])


@pytest.mark.parametrize("mutate,want", [
    (_add, ["a", "b", "m", "z"]), (_update, ["b", "m", "z"]),
    (_delete, ["b", "z"]), (_replace, ["c", "q"])],
    ids=["add", "update", "delete", "replace"])
def test_lister_keeps_its_list_until_the_store_changes(mutate, want):
    store = Store()
    for name in ("z", "m", "b"):
        store.add(mk_node(name))
    lister = _StoreMinionLister(store)
    first = lister.list()
    assert [n.metadata.name for n in first.items] == ["b", "m", "z"]
    token = store.token()
    for _ in range(3):
        again = lister.list()
        assert again is first and again.items is first.items
    assert store.token() == token
    mutate(store)
    assert store.token() != token
    after = lister.list()
    assert after.items is not first.items
    assert [n.metadata.name for n in after.items] == want
    assert lister.list() is after
    # what was handed out before is not written under its holder
    assert [n.metadata.name for n in first.items] == ["b", "m", "z"]


# -- (e) through the wave scheduler and the incremental encoder ---------------

def test_static_nodes_build_once_and_a_new_node_is_used_at_once():
    m = Master()
    client = Client(InProcessTransport(m))
    for i in range(3):
        client.nodes().create(mk_node(f"n{i}"))
    factory = ConfigFactory(client)
    sched = BatchScheduler(factory.create(), factory, client, wave_size=4,
                           wave_linger_s=0.01)
    try:
        listed = _listed()
        waves = 0
        for i in range(24):
            client.pods().create(mk_pod(f"p{i}"))
            assert _wait(lambda: len(factory.pod_queue) == 1)
            assert sched.schedule_wave(timeout=1.0) == 1
            waves += 1
        assert waves >= 20
        assert sched._encoder.op_counts["node_rebuilds"] == 1
        # the three nodes are full of nothing yet, a fourth far larger one
        # takes the next pod by LeastRequested as soon as the watch has
        # said it is there: no poll period to wait out
        big = mk_node("big")
        big.spec.capacity = {"cpu": Quantity("512"),
                             "memory": Quantity("1024Gi")}
        t0 = time.monotonic()
        client.nodes().create(big)
        assert _wait(lambda: "big" in _names(factory.node_store), 2.0)
        hop = time.monotonic() - t0
        client.pods().create(mk_pod("onto-big"))
        assert _wait(lambda: len(factory.pod_queue) == 1)
        assert sched.schedule_wave(timeout=1.0) == 1
        assert client.pods().get("onto-big").spec.host == "big"
        assert sched._encoder.op_counts["node_rebuilds"] == 2
        assert hop < 1.0, f"the node took {hop:.2f}s to reach the scheduler"
        assert _listed() == listed
    finally:
        sched.stop()
        factory.stop(join=True)
