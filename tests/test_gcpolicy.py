"""util/gcpolicy.py: what survived a full collection leaves the collector's
walk (``gc.freeze()`` from a ``gc.callbacks`` hook), a whole walk of the
frozen objects comes once a period, and five ``process_gc_*`` series say
what the collector did. The policy is process-wide, so every test starts
from a process without it and puts back what it found.
"""

import gc
import importlib
import threading
import time
import weakref

import pytest

from kubernetes_tpu.util import gcpolicy, metrics


class Node:
    """A collector-tracked object that can be one of a cycle."""

    def __init__(self):
        self.other = None


def cycle() -> Node:
    a, b = Node(), Node()
    a.other, b.other = b, a
    return a


def series(name: str, **labels) -> float:
    """One sample of the default registry's text, as /metrics renders it."""
    want = name + ("{" + ",".join(f'{k}="{v}"' for k, v in labels.items())
                   + "}" if labels else "")
    for line in metrics.default_registry().render_text().splitlines():
        sample, _, value = line.rpartition(" ")
        if sample == want:
            return float(value)
    raise AssertionError(f"{want} is not rendered")


def hooks() -> int:
    return gc.callbacks.count(gcpolicy._on_gc)


@pytest.fixture(autouse=True)
def fresh_policy():
    """Other modules of this worker may have started an APIServer: take
    the policy out for the test and put it back after."""
    was_installed = gcpolicy._installed
    gcpolicy.reset()
    yield
    gcpolicy.reset()
    if was_installed:
        gcpolicy.ensure()


class Clock:
    def __init__(self):
        self.now = 1000.0

    def __call__(self) -> float:
        return self.now


@pytest.fixture
def clock(monkeypatch):
    c = Clock()
    monkeypatch.setattr(gcpolicy, "_clock", c)
    return c


def test_ensure_twice_leaves_one_callback():
    assert hooks() == 0
    gcpolicy.ensure()
    gcpolicy.ensure()
    assert hooks() == 1


def test_ensure_from_many_threads_installs_once():
    threads = [threading.Thread(target=gcpolicy.ensure) for _ in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30.0)
    assert not any(t.is_alive() for t in threads)
    assert hooks() == 1


def test_ensure_freezes_what_the_process_holds():
    held = [Node() for _ in range(1000)]
    gcpolicy.ensure()
    assert gc.get_freeze_count() >= len(held)
    unfrozen = {id(o) for o in gc.get_objects()}
    assert not any(id(o) in unfrozen for o in held)


def test_full_collection_empties_the_oldest_generation():
    gcpolicy.ensure()
    frozen = gc.get_freeze_count()
    held = [Node() for _ in range(1000)]
    gc.collect()
    assert gc.get_freeze_count() >= frozen + len(held)
    oldest = {id(o) for o in gc.get_objects(generation=2)}
    assert not any(id(o) in oldest for o in held)
    # next to nothing is left there: what the callbacks themselves made
    assert len(oldest) < 100


def test_second_full_collection_examines_only_what_came_since():
    gcpolicy.ensure()
    first = [Node() for _ in range(1000)]
    gc.collect()
    frozen = gc.get_freeze_count()
    second = [Node() for _ in range(500)]
    walked = {id(o) for o in gc.get_objects()}   # the generations, unfrozen
    assert all(id(o) in walked for o in second)
    assert not any(id(o) in walked for o in first)
    gc.collect()
    grew = gc.get_freeze_count() - frozen
    assert len(second) <= grew < len(second) + len(first)


def test_cycle_dropped_after_freeze_waits_for_the_whole_walk(clock):
    gcpolicy.ensure()
    a = cycle()
    gone = weakref.ref(a)
    gc.collect()                 # alive at a full collection: frozen
    del a
    walks = series("process_gc_whole_walks_total")
    clock.now += gcpolicy.WHOLE_WALK_PERIOD_S - 1.0
    gc.collect()
    assert gone() is not None, "a frozen cycle was walked before the period"
    assert series("process_gc_whole_walks_total") == walks
    clock.now += 1.0
    gc.collect()
    assert gone() is None, "the whole walk did not reclaim the cycle"
    assert series("process_gc_whole_walks_total") == walks + 1
    # and the survivors are frozen again, the period runs anew
    assert not gc.get_objects(generation=2)
    b = cycle()
    kept = weakref.ref(b)
    gc.collect()
    del b
    gc.collect()
    assert kept() is not None


def test_cycle_never_frozen_is_reclaimed_by_any_full_collection():
    gcpolicy.ensure()
    gone = weakref.ref(cycle())
    gc.collect()
    assert gone() is None


@pytest.mark.parametrize("name,labels", [
    ("process_gc_pause_seconds_count", {"generation": "0"}),
    ("process_gc_pause_seconds_count", {"generation": "1"}),
    ("process_gc_pause_seconds_count", {"generation": "2"}),
    ("process_gc_pause_seconds_sum", {"generation": "2"}),
    ("process_gc_pause_seconds_total", {}),
    ("process_gc_collected_total", {"generation": "2"}),
    ("process_gc_whole_walks_total", {}),
])
def test_series_move(clock, name, labels):
    gcpolicy.ensure()
    before = series(name, **labels)
    clock.now += gcpolicy.WHOLE_WALK_PERIOD_S
    for generation in (0, 1, 2):
        cycle()                  # garbage for this generation to find
        gc.collect(generation)
    assert series(name, **labels) > before


def test_series_count_what_the_interpreter_counts():
    gcpolicy.ensure()
    stats = gc.get_stats()
    mine = [series("process_gc_pause_seconds_count", generation=g)
            for g in range(3)]
    total = series("process_gc_pause_seconds_total")
    junk = []
    for _ in range(20000):       # trips generation 0 and 1 on its own
        junk.append(cycle())
        if len(junk) > 50:
            junk.clear()
    gc.collect()
    for g, (was, now) in enumerate(zip(stats, gc.get_stats())):
        assert series("process_gc_pause_seconds_count", generation=g) \
            - mine[g] == now["collections"] - was["collections"]
        assert series("process_gc_pause_seconds_bucket", generation=g,
                      le="+Inf") \
            == series("process_gc_pause_seconds_count", generation=g)
    sums = sum(series("process_gc_pause_seconds_sum", generation=g)
               for g in range(3))
    assert series("process_gc_pause_seconds_total") == pytest.approx(sums)
    assert series("process_gc_pause_seconds_total") > total


def test_frozen_objects_renders():
    gcpolicy.ensure()
    held = [Node() for _ in range(1000)]
    before = series("process_gc_frozen_objects")
    gc.collect()
    after = series("process_gc_frozen_objects")
    assert after >= before + len(held)
    assert abs(after - gc.get_freeze_count()) < 1000


def test_reset_takes_the_hook_out_and_unfreezes():
    gcpolicy.ensure()
    assert gc.get_freeze_count() > 0
    gcpolicy.reset()
    assert hooks() == 0
    assert gc.get_freeze_count() == 0
    held = [Node() for _ in range(5000)]
    gc.collect()
    # the interpreter itself keeps its few hundred immortal objects there
    assert gc.get_freeze_count() < len(held)
    oldest = {id(o) for o in gc.get_objects(generation=2)}
    assert all(id(o) in oldest for o in held)
    gcpolicy.reset()             # and may be called where nothing is installed


def test_hook_takes_no_lock_a_render_may_hold():
    """A collection can trip in a thread that holds a series' lock (a
    render builds lists under it): a hook that observed through the
    registry would wait for its own thread for ever."""
    gcpolicy.ensure()
    done = threading.Event()

    def collect_under_the_locks():
        with gcpolicy._PAUSE._lock, gcpolicy._PAUSE_TOTAL._lock, \
                gcpolicy._COLLECTED._lock, gcpolicy._collect_lock:
            gc.collect()
        done.set()

    t = threading.Thread(target=collect_under_the_locks, daemon=True)
    t.start()
    t.join(timeout=10.0)
    assert done.is_set()


def test_renders_and_collections_from_many_threads():
    gcpolicy.ensure()
    stats = gc.get_stats()
    mine = [series("process_gc_pause_seconds_count", generation=g)
            for g in range(3)]
    deadline = time.monotonic() + 1.0
    errors = []

    def allocate():
        junk = []
        while time.monotonic() < deadline:
            junk.append(cycle())
            if len(junk) > 200:
                junk.clear()

    def render():
        last = 0.0
        try:
            while time.monotonic() < deadline:
                now = series("process_gc_pause_seconds_total")
                assert now >= last
                last = now
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(e)

    threads = [threading.Thread(target=allocate) for _ in range(6)] + \
              [threading.Thread(target=render) for _ in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30.0)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    for g, (was, now) in enumerate(zip(stats, gc.get_stats())):
        assert series("process_gc_pause_seconds_count", generation=g) \
            - mine[g] == now["collections"] - was["collections"]


def test_histogram_load_wants_a_count_for_every_bucket():
    h = metrics.Registry().histogram("h", "", ("k",), buckets=(1.0, 2.0))
    h.load([1, 2, 3], 7.5, "a")
    assert h.count("a") == 6 and h.sum("a") == 7.5
    assert 'h_bucket{k="a",le="2"} 3' in h.render()
    with pytest.raises(ValueError):
        h.load([1, 2], 1.0, "a")


def test_apiserver_start_installs_it():
    from kubernetes_tpu.apiserver.http import APIServer
    from kubernetes_tpu.apiserver.master import Master, MasterConfig
    assert hooks() == 0
    srv = APIServer(Master(MasterConfig()), host="127.0.0.1", port=0).start()
    try:
        assert hooks() == 1
    finally:
        srv.stop()


class Installed(Exception):
    pass


@pytest.mark.parametrize("module,server", [
    ("scheduler", "scheduler_server"), ("solverd", "solverd_server"),
    ("storeserver", "main"),
    ("controller_manager", "controller_manager_server"),
    ("kubelet", "kubelet_server"), ("proxy", "proxy_server"),
    ("descheduler", "descheduler_server")])
def test_mains_without_an_apiserver_install_it_first(monkeypatch, module,
                                                     server):
    def ensure():
        raise Installed
    monkeypatch.setattr(gcpolicy, "ensure", ensure)
    cmd = importlib.import_module(f"kubernetes_tpu.cmd.{module}")
    with pytest.raises(Installed):   # before a flag is parsed
        getattr(cmd, server)(["--no-such-flag"])
