"""Client-side list-watch caches (ref: pkg/client/cache/).

- ``Store``: thread-safe keyed object store (store.go)
- ``FIFO``: Store-shaped producer/consumer queue with blocking Pop (fifo.go)
- ``Reflector``: list+watch a resource into a Store, resuming from
  resourceVersion and relisting when the watch expires (reflector.go:43-91)
- ``ListWatch``: the pluggable list/watch source (listwatch.go)
- Typed listers over a Store (listers.go)

Every control loop (scheduler, controllers, kubelet apiserver-source) runs on
these primitives, exactly as in the reference.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

from kubernetes_tpu import watch as watchpkg
from kubernetes_tpu.api import errors
from kubernetes_tpu.api import labels as labels_pkg
from kubernetes_tpu.api import types as api
from kubernetes_tpu.api.meta import accessor
from kubernetes_tpu.util import tracing
from kubernetes_tpu.util.retry import Backoff

__all__ = ["meta_namespace_key_func", "Store", "FIFO", "ListWatch", "Reflector",
           "StorePodLister", "StoreNodeLister", "StoreServiceLister"]


def meta_namespace_key_func(obj: Any) -> str:
    """<namespace>/<name> key (ref: store.go MetaNamespaceKeyFunc)."""
    m = obj.metadata
    return f"{m.namespace}/{m.name}" if m.namespace else m.name


class Store:
    """Threadsafe keyed store (ref: cache.Store).

    Beyond the reference's interface the store keeps a bounded CHANGELOG
    of mutations so consumers can stay O(changed-objects) per cycle
    instead of re-reading O(all-objects) — the seam the wave scheduler's
    incremental encoder rides under churn (the reference's analog cost is
    MapPodsToMachines rebuilding the full host map every cycle,
    ref: pkg/scheduler/predicates.go:354-375). ``delta_since(token)``
    returns the (op, obj) events after ``token``; a relist (replace) or a
    fallen-behind token yields None — resync by reading ``list()``."""

    # ~16s of events at 1k-churn rates — consumers poll every wave, and a
    # fallen-behind token just triggers a list() resync; a bigger window
    # would pin that many dead object versions in memory for nothing
    _LOG_MAX = 1 << 14

    def __init__(self, key_func: Callable[[Any], str] = meta_namespace_key_func):
        self._lock = threading.RLock()
        self._items: Dict[str, Any] = {}
        self.key_func = key_func
        self._version = 0
        self._log: deque = deque(maxlen=self._LOG_MAX)  # (ver, op, obj)
        self._observers: list = []

    def subscribe(self, fn: Callable[[Any], None]) -> None:
        """Register a post-set observer: called with each object as it
        lands via add/update (NOT replace — a relist is a resync, not a
        delivery). The seam the wave scheduler uses to timestamp when its
        own watch stream observes a bound pod
        (``pod_watch_observe_seconds``). Observers run on the reflector's
        delivery thread, outside the store lock — they must be cheap and
        must not raise."""
        with self._lock:
            self._observers.append(fn)

    def add(self, obj: Any) -> None:
        with self._lock:
            self._items[self.key_func(obj)] = obj
            self._version += 1
            self._log.append((self._version, "set", obj))
            observers = self._observers
        for fn in observers:
            try:
                fn(obj)
            except Exception:
                pass

    def update(self, obj: Any) -> None:
        self.add(obj)

    def delete(self, obj: Any) -> None:
        with self._lock:
            prev = self._items.pop(self.key_func(obj), None)
            if prev is not None:
                self._version += 1
                self._log.append((self._version, "delete", prev))

    def token(self) -> int:
        """Current changelog position for a later delta_since."""
        with self._lock:
            return self._version

    def delta_since(self, token: int):
        """-> (events, new_token) with events = [(op, obj), ...] in order,
        or None when the token predates the retained window (log overflow
        or a replace()) — the caller must resync via list()."""
        with self._lock:
            if token == self._version:
                return [], token
            if not self._log or self._log[0][0] > token + 1:
                return None
            return ([(op, obj) for ver, op, obj in self._log if ver > token],
                    self._version)

    def get(self, obj: Any) -> Optional[Any]:
        return self.get_by_key(self.key_func(obj))

    def get_by_key(self, key: str) -> Optional[Any]:
        with self._lock:
            return self._items.get(key)

    def list(self) -> List[Any]:
        with self._lock:
            return list(self._items.values())

    def list_keys(self) -> List[str]:
        with self._lock:
            return list(self._items.keys())

    @staticmethod
    def _same_version(prev: Any, cur: Any) -> bool:
        """True when a relist returned the SAME object state: identical
        identity, or same uid + same non-empty resourceVersion. Non-API
        objects (no metadata) compare by identity only — conservative:
        a false negative just re-logs one set event."""
        if prev is cur:
            return True
        try:
            pm, cm = prev.metadata, cur.metadata
            return (pm.uid == cm.uid and pm.resource_version != ""
                    and pm.resource_version == cm.resource_version)
        except AttributeError:
            return False

    def replace(self, objs: List[Any]) -> None:
        """Atomically reset contents (ref: store.go Replace — used by
        relist). kube-slipstream: instead of clearing the changelog (the
        pre-r19 contract, which made every watch 410 / stream reset cost
        consumers a full O(all-objects) resync), the new list is DIFFED
        against the cache and only the real changes are appended — a
        relist that missed k events costs delta consumers O(k), and the
        incremental encoder's journal replay rides straight through it.
        Only when the diff itself outgrows the retained window does
        replace fall back to the old contract (clear the log, invalidate
        every token). Observers are still NOT notified — a relist is a
        resync, not a delivery."""
        with self._lock:
            new = {self.key_func(o): o for o in objs}
            events: List[tuple] = []
            for key, prev in self._items.items():
                cur = new.get(key)
                if cur is None:
                    events.append(("delete", prev))
                elif not self._same_version(prev, cur):
                    try:
                        uid_changed = prev.metadata.uid != cur.metadata.uid
                    except AttributeError:
                        uid_changed = False
                    if uid_changed:
                        # name reuse across the gap: the old uid must be
                        # retired or its resources leak in the encoder
                        events.append(("delete", prev))
                    events.append(("set", cur))
            for key, cur in new.items():
                if key not in self._items:
                    events.append(("set", cur))
            self._items = new
            if len(events) >= self._LOG_MAX:
                # gap wider than the window: old contract (tokens die)
                self._version += 1
                self._log.clear()
                return
            for op, obj in events:
                self._version += 1
                self._log.append((self._version, op, obj))

    def __len__(self):
        with self._lock:
            return len(self._items)


class FIFO:
    """Producer/consumer queue keyed like a Store (ref: fifo.go).

    Items added while present are coalesced (update-in-place keeps queue
    position); Pop blocks until an item is available.

    ``wait_hist`` (a metrics.Histogram) is observed once per popped item
    with the seconds from the key's FIRST add — a coalesced re-add keeps
    the first stamp — to the pop that hands it out.
    """

    def __init__(self,
                 key_func: Callable[[Any], str] = meta_namespace_key_func,
                 wait_hist=None):
        self._cond = threading.Condition()
        self._items: Dict[str, Any] = {}
        self._queue: List[str] = []
        self.key_func = key_func
        self._wait_hist = wait_hist
        self._added: Dict[str, float] = {}   # key -> monotonic first add

    def add(self, obj: Any) -> None:
        with self._cond:
            key = self.key_func(obj)
            if key not in self._items:
                self._queue.append(key)
                if self._wait_hist is not None:
                    self._added[key] = time.monotonic()
            self._items[key] = obj
            self._cond.notify()

    update = add

    def delete(self, obj: Any) -> None:
        with self._cond:
            key = self.key_func(obj)
            self._items.pop(key, None)
            self._added.pop(key, None)
            # key stays in _queue; Pop skips missing items (ref: fifo.go Pop)

    def get_by_key(self, key: str) -> Optional[Any]:
        with self._cond:
            return self._items.get(key)

    def list(self) -> List[Any]:
        with self._cond:
            return list(self._items.values())

    def replace(self, objs: List[Any]) -> None:
        with self._cond:
            self._items = {self.key_func(o): o for o in objs}
            self._queue = list(self._items.keys())
            if self._wait_hist is not None:
                now = time.monotonic()
                self._added = {k: self._added.get(k, now)
                               for k in self._queue}
            self._cond.notify_all()

    def pop(self, timeout: Optional[float] = None) -> Any:
        """Blocking pop of the oldest item (ref: fifo.go Pop)."""
        deadline = time.monotonic() + timeout if timeout is not None else None
        with self._cond:
            while True:
                while self._queue:
                    key = self._queue.pop(0)
                    if key in self._items:
                        if self._wait_hist is not None:
                            now = time.monotonic()
                            self._wait_hist.observe(
                                now - self._added.pop(key, now))
                        return self._items.pop(key)
                remaining = None
                if deadline is not None:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        raise TimeoutError("FIFO.pop timed out")
                self._cond.wait(timeout=remaining)

    def __len__(self):
        with self._cond:
            return len(self._items)


class ListWatch:
    """Pluggable list+watch source (ref: listwatch.go).

    ``list_fn()`` returns a list object (items + metadata.resource_version);
    ``watch_fn(resource_version)`` returns a watch.Watcher.
    """

    def __init__(self, list_fn, watch_fn):
        self.list_fn = list_fn
        self.watch_fn = watch_fn


class Reflector:
    """Mirrors a resource into a Store via list+watch (ref: reflector.go:43-91).

    list -> Store.replace -> watch(rv) -> apply events, tracking the last seen
    resourceVersion; when the watch ends or the version window expires
    (ErrIndexOutdated / 410 Gone), relist and resume. Crash-only: any error
    backs off (capped exponential + jitter, reset on a successful
    iteration — an apiserver respawn must cost a few retries, not a
    50 ms hammer loop against a refused port) and starts over
    (ref: util.Forever usage, reflector.go:84).
    """

    def __init__(self, listwatch: ListWatch, store, resync_period: float = 0.0,
                 name: str = "reflector"):
        self.lw = listwatch
        self.store = store
        self.resync_period = resync_period
        self.name = name
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._backoff = Backoff(base=0.05, cap=2.0)
        self.last_sync_resource_version = ""
        # kube-slipstream: streams re-opened at the last seen rv instead
        # of relisting (visible in tests and the debug narrative)
        self.watch_resumes = 0
        self._listed = threading.Event()

    def run(self) -> "Reflector":
        self._thread = threading.Thread(target=self._run_loop, daemon=True, name=self.name)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()

    def wait_listed(self, timeout: Optional[float] = None) -> bool:
        """Wait for the first LIST after run(): True once it has landed in
        the store, or failed (the run loop is then backing off towards its
        next try), or the reflector was stopped before either. A caller
        that must not hand out an empty store waits here (the scheduler's
        node source, as the reference's poller listed once before it
        returned)."""
        return self._listed.wait(timeout)

    def join(self, timeout: Optional[float] = None) -> bool:
        """Wait for the run loop to exit after stop(). Returns True once the
        thread is down — after which no further event can be applied to the
        store (the graceful-shutdown contract callers need to freeze a
        cache deterministically)."""
        t = self._thread
        if t is None:
            return True    # never started
        t.join(timeout)
        return not t.is_alive()

    def _run_loop(self) -> None:
        tracing.role("reflector")
        try:
            while not self._stop.is_set():
                try:
                    self._list_and_watch()
                    self._backoff.reset()  # listed fine: source is healthy
                except Exception:
                    self._listed.set()
                    if self._stop.is_set():
                        return
                    # interruptible backoff: stop() during an outage must
                    # not hold the thread for the full capped delay
                    if self._stop.wait(self._backoff.next()):
                        return
        finally:
            self._listed.set()
            tracing.role_end()

    def _list_and_watch(self) -> None:
        lst = self.lw.list_fn()
        rv = lst.metadata.resource_version
        self.store.replace(lst.items)
        self._listed.set()
        self.last_sync_resource_version = rv
        resync_deadline = (time.monotonic() + self.resync_period
                           if self.resync_period else None)
        while not self._stop.is_set():
            try:
                w = self.lw.watch_fn(rv)
            except errors.StatusError as e:
                if errors.is_resource_expired(e):
                    return  # 410 Gone: relist
                raise
            progressed = False
            try:
                while not self._stop.is_set():
                    if resync_deadline and time.monotonic() >= resync_deadline:
                        return  # periodic full relist
                    try:
                        ev = w.next_event(timeout=0.2)
                    except Exception:
                        continue
                    if ev is None:
                        # kube-slipstream: a benign stream close (idle
                        # timeout, apiserver rotation) after at least one
                        # rv-advancing event resumes the watch at the last
                        # seen rv — no relist, the store changelog stays
                        # continuous and delta consumers replay through.
                        # A close before any progress, a 410, or an ERROR
                        # event still relists (the old crash-only path).
                        if progressed:
                            self.watch_resumes += 1
                            break  # re-open watch_fn(rv) without relist
                        return  # stream closed cold: relist
                    if ev.type == watchpkg.ERROR:
                        return
                    obj = ev.object
                    if ev.type == watchpkg.ADDED:
                        self.store.add(obj)
                    elif ev.type == watchpkg.MODIFIED:
                        self.store.update(obj)
                    elif ev.type == watchpkg.DELETED:
                        self.store.delete(obj)
                    new_rv = accessor.resource_version(obj)
                    if new_rv:
                        rv = new_rv
                        self.last_sync_resource_version = rv
                        progressed = True
            finally:
                w.stop()


# -- typed listers (ref: listers.go) ---------------------------------------


class StorePodLister:
    def __init__(self, store: Store):
        self.store = store

    def list(self, selector: Optional[labels_pkg.Selector] = None) -> List[api.Pod]:
        pods = self.store.list()
        if selector is None:
            return pods
        return [p for p in pods if selector.matches(p.metadata.labels)]


class StoreNodeLister:
    def __init__(self, store: Store):
        self.store = store

    def list(self) -> api.NodeList:
        return api.NodeList(items=self.store.list())


class StoreServiceLister:
    def __init__(self, store: Store):
        self.store = store

    def get_pod_services(self, pod: api.Pod) -> List[api.Service]:
        """Services whose selector matches the pod (ref: listers.go
        StoreToServiceLister.GetPodServices)."""
        out = []
        for svc in self.store.list():
            if svc.metadata.namespace != pod.metadata.namespace:
                continue
            if not svc.spec.selector:
                continue
            if labels_pkg.selector_from_set(svc.spec.selector).matches(pod.metadata.labels):
                out.append(svc)
        return out
