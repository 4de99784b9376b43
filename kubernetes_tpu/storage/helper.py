"""StoreHelper — typed CRUD over the versioned KV.

Rebuild of the reference's EtcdHelper (ref: pkg/tools/etcd_helper.go:36-345 +
etcd_helper_watch.go:64-95): encodes/decodes API objects with the runtime
Scheme, maps the store's modified_index to ObjectMeta.resource_version, and
provides the read-modify-CAS ``atomic_update`` loop every registry and
controller relies on for optimistic concurrency.
"""

from __future__ import annotations

import threading
from collections import OrderedDict

from kubernetes_tpu.runtime.clone import deep_clone
from typing import Any, Callable, Optional, Type

from kubernetes_tpu import watch as watchpkg
from kubernetes_tpu.api import errors
from kubernetes_tpu.api.meta import accessor
from kubernetes_tpu.util import metrics
from kubernetes_tpu.util import reqparts
from kubernetes_tpu.storage.memstore import (
    ErrCASConflict,
    ErrIndexOutdated,
    ErrKeyExists,
    ErrKeyNotFound,
    MemStore,
)

__all__ = ["StoreHelper", "parse_watch_resource_version"]

# How a revision came into a decode cache: "codec" ran the scheme over
# the stored bytes, "write" is a write's own object handed on (_landed).
_CACHED = metrics.default_registry().counter(
    "storage_decode_cache_total",
    "Revisions put into a StoreHelper's decode cache, by source",
    ("source",))


def parse_watch_resource_version(rv: str) -> int:
    """ref: pkg/tools/etcd_helper_watch.go:47-57 ParseWatchResourceVersion —
    '' or '0' means "from now"; otherwise watch resumes after rv."""
    if not rv or rv == "0":
        return 0
    try:
        return int(rv)
    except ValueError:
        raise errors.new_invalid("", rv, [ValueError(f"invalid resourceVersion {rv!r}")])


class StoreHelper:
    # (key, modified_index) -> decoded object. A stored revision is
    # immutable, so its decode is too: lists re-reading a stable cluster
    # and watch pumps fanning one event out to several watchers hit the
    # cache and pay a dict lookup instead of a full codec decode (~170us)
    # — the difference between 250 and 1000 pods/s of churn through the
    # live stack. Bounded FIFO.
    #
    # READ-SHARING CONTRACT: list and watch return the CACHED objects
    # themselves, not copies (the per-read deep_clone was ~13 clones per
    # churned pod — the single largest per-pod CPU item). Safe because
    # bulk/stream consumers only enumerate or encode: the HTTP path
    # serializes to wire bytes, the in-process transport deep-clones both
    # directions (client/client.py InProcessTransport._copy), and
    # controllers build fresh objects from what they read. The only
    # in-tree mutation of a served bulk read is master._stamp_self_links,
    # which writes the same deterministic string every time (idempotent).
    # SINGLE-object reads (extract_obj/delete_obj) stay isolated: the
    # get-mutate-set idiom is legitimate there and they are off the churn
    # hot path. atomic_update isolates before calling update_fn; the
    # DELETED-event resourceVersion rewrite clones explicitly.
    #
    # WHO MAY SEED THE CACHE: _cache, from two callers. _decode, with what
    # the codec made of the stored bytes; and _landed, the one seam every
    # write verb ends in, with a COPY of the object it just stored
    # (Scheme.deep_copy: what decoding that object's encoding gives,
    # field for field, without the wire) — never the caller's object,
    # which the caller goes on to own and mutate (registries stamp it,
    # PodStatusREST aliases the request's status into it, in-process
    # clients hand in whatever they built). Both finish the object the
    # same way — resourceVersion, then the linkers — before it becomes
    # visible, so a reader cannot tell which of the two it was handed.
    #
    # Sized to hold a full-shape churn working set (50k pods): at 8192 a
    # pod created early in the run was evicted by the time its bind
    # committed, so every batched bind paid a cold decode + the bind
    # event's prev_kv decode — two full codec passes back on the hot
    # path the cache exists to remove.
    _DECODE_CACHE_MAX = 65536
    # A written revision's wire dict waits here for the response or the
    # bind's frame seed, microseconds as a rule; a write nobody answers
    # over HTTP (in-process clients) leaves its dict to fall off the end.
    # Holds more than the largest wave's bindings:batch.
    _WRITTEN_MAX = 2048

    def __init__(self, store: MemStore, scheme):
        self.store = store
        self.scheme = scheme
        self._decode_cache: "OrderedDict" = OrderedDict()
        self._written: "OrderedDict" = OrderedDict()  # rv -> wire dict
        self._decode_lock = threading.Lock()          # guards both
        self._linkers: list = []  # (key prefix, decorate_fn)

    def register_linker(self, prefix: str, fn) -> None:
        """Register a decorator run ONCE per cached revision at decode time
        (the master registers selfLink stamping per resource prefix). With
        shared reads, decoration must happen before the object becomes
        visible — a post-read stamp would mutate an object other readers
        (watch pumps, concurrent lists) already see, making wire output
        order-dependent."""
        self._linkers.append((prefix if prefix.endswith("/") else prefix + "/",
                              fn))

    # -- encode/decode ------------------------------------------------------
    def _decode(self, kv, isolate: bool = False) -> Any:
        ck = (kv.key, kv.modified_index)
        with self._decode_lock:
            cached = self._decode_cache.get(ck)
        if cached is None:
            cached = self._cache(kv, self.scheme.decode(kv.value), "codec")
        return deep_clone(cached) if isolate else cached

    def _cache(self, kv, obj, source: str) -> Any:
        """Finish ``obj`` as revision ``kv`` (its resourceVersion, then the
        linkers) and put it into the decode cache: the one way in."""
        accessor.set_resource_version(obj, str(kv.modified_index))
        for prefix, fn in self._linkers:
            if kv.key.startswith(prefix):
                fn(obj)
                break
        _CACHED.inc(source)
        with self._decode_lock:
            self._decode_cache[(kv.key, kv.modified_index)] = obj
            while len(self._decode_cache) > self._DECODE_CACHE_MAX:
                self._decode_cache.popitem(last=False)
        return obj

    def _walk(self, obj) -> "tuple[str, tuple]":
        """One walk a write, before the store is asked: the bytes the store
        is to hold, and what ``_landed`` hands on once it holds them —
        ``obj``, its wire dict in the storage version (the bytes are that
        dict dumped) and the copy that will seed the decode cache. The copy
        is made here and not after the write, so that what is left to do
        between a revision's event and its place in the caches is a
        stamp and two inserts: a watcher that gets there first runs the
        codec over bytes written microseconds before."""
        # resourceVersion is storage metadata, not payload: clear before
        # encoding, like the reference (etcd_helper.go:236 Versioner).
        rv = accessor.resource_version(obj)
        accessor.set_resource_version(obj, "")
        try:
            wire = self.scheme.encode_to_wire(obj)
        finally:
            accessor.set_resource_version(obj, rv)
        return (self.scheme.wire_to_json(wire),
                (obj, wire, self.scheme.deep_copy(obj)))

    def _landed(self, kv, obj, wire: dict, copy) -> Any:
        """The write seam: ``obj``, walked by ``_walk``, is revision ``kv``
        of the store. Stamps the caller's object in place, like the
        reference (etcd_helper.go CreateObj leaves the passed
        runtime.Object as the result), seeds the decode cache with the
        copy — the caller goes on to own ``obj`` — and keeps ``wire`` for
        whoever answers this write (take_wire)."""
        rv = str(kv.modified_index)
        accessor.set_resource_version(obj, rv)
        with self._decode_lock:
            raced = (kv.key, kv.modified_index) in self._decode_cache
            self._written[rv] = wire
            while len(self._written) > self._WRITTEN_MAX:
                self._written.popitem(last=False)
        if not raced:  # else a watcher was quicker and ran the codec
            self._cache(kv, copy, "write")
        return obj

    def take_wire(self, obj) -> Optional[dict]:
        """The wire dict, in the storage version, of the revision ``obj``
        is — once, to the caller that answers the write that made it: the
        write's own walk, with the two metadata keys that walk could not
        know (the store bytes carry no resourceVersion; the master stamps
        selfLink on what it returns) as ``obj`` carries them. None when
        this helper did not write the revision or it was taken already."""
        m = getattr(obj, "metadata", None)
        rv = getattr(m, "resource_version", "")
        if not rv or not hasattr(m, "name"):  # a list's is a store index
            return None
        with self._decode_lock:
            wire = self._written.pop(rv, None)
        if wire is None:
            return None
        meta = wire["metadata"]
        meta["resourceVersion"] = rv
        if m.self_link:
            meta["selfLink"] = m.self_link
        return wire

    # -- CRUD ---------------------------------------------------------------
    # The write verbs take the HTTP request's clock by part (``parts``,
    # util/reqparts.py) and mark what only they can tell apart: the walk
    # (``walk``), and the store with the reads before it and ``_landed``
    # after it (``store``). They leave it at ``other``.
    def create_obj(self, key: str, obj: Any, ttl: Optional[float] = None,
                   parts=reqparts.NO_PARTS) -> Any:
        """ref: etcd_helper.go:205 CreateObj."""
        parts.mark(reqparts.WALK)
        encoded, walked = self._walk(obj)
        parts.mark(reqparts.STORE)
        try:
            kv = self.store.create(key, encoded, ttl=ttl)
        except ErrKeyExists:
            raise errors.new_already_exists(accessor.kind(obj), accessor.name(obj))
        out = self._landed(kv, *walked)
        parts.mark(reqparts.OTHER)
        return out

    def set_obj(self, key: str, obj: Any, ttl: Optional[float] = None,
                parts=reqparts.NO_PARTS) -> Any:
        """Write; CAS on the object's resourceVersion when set
        (ref: etcd_helper.go:236 SetObj)."""
        rv = accessor.resource_version(obj)
        parts.mark(reqparts.WALK)
        encoded, walked = self._walk(obj)
        parts.mark(reqparts.STORE)
        try:
            if rv:
                kv = self.store.compare_and_swap(key, encoded, int(rv), ttl=ttl)
            else:
                kv = self.store.set(key, encoded, ttl=ttl)
        except ErrCASConflict:
            raise errors.new_conflict(accessor.kind(obj), accessor.name(obj))
        except ErrKeyNotFound:
            raise errors.new_not_found(accessor.kind(obj), accessor.name(obj))
        out = self._landed(kv, *walked)
        parts.mark(reqparts.OTHER)
        return out

    def extract_obj(self, key: str, kind: str = "", name: str = "") -> Any:
        """ref: etcd_helper.go:144 ExtractObj."""
        try:
            kv = self.store.get(key)
        except ErrKeyNotFound:
            raise errors.new_not_found(kind or "resource", name or key)
        return self._decode(kv, isolate=True)

    def extract_to_list(self, prefix: str, list_type: Type) -> Any:
        """ref: etcd_helper.go:78 ExtractToList — items + list resourceVersion."""
        kvs, index = self.store.list(prefix)
        lst = list_type()
        lst.items = [self._decode(kv) for kv in kvs]
        lst.metadata.resource_version = str(index)
        return lst

    def delete_obj(self, key: str, kind: str = "", name: str = "") -> Any:
        try:
            prev = self.store.delete(key)
        except ErrKeyNotFound:
            raise errors.new_not_found(kind or "resource", name or key)
        return self._decode(prev, isolate=True)

    def atomic_update(self, key: str, obj_type: Type,
                      update_fn: Callable[[Any], Any],
                      ignore_not_found: bool = False,
                      ttl: Optional[float] = None,
                      max_retries: int = 100,
                      parts=reqparts.NO_PARTS) -> Any:
        """Read-modify-CAS loop (ref: etcd_helper.go:311-345 AtomicUpdate).

        ``update_fn`` receives the current object (or a fresh ``obj_type()``
        when absent and ignore_not_found) and returns the desired object; on
        CAS conflict the loop re-reads and retries. This is THE concurrency
        primitive: the scheduler's bind path, status updates, and quota
        decrements all go through it.
        """
        for _ in range(max_retries):
            parts.mark(reqparts.STORE)
            try:
                kv = self.store.get(key)
                # isolate: update_fn mutates what it is handed
                current = self._decode(kv, isolate=True)
                prev_index: Optional[int] = kv.modified_index
            except ErrKeyNotFound:
                if not ignore_not_found:
                    raise errors.new_not_found(obj_type.__name__, key)
                current = obj_type()
                prev_index = None
            desired = update_fn(current)
            parts.mark(reqparts.WALK)
            encoded, walked = self._walk(desired)
            parts.mark(reqparts.STORE)
            try:
                if prev_index is None:
                    kv = self.store.create(key, encoded, ttl=ttl)
                else:
                    kv = self.store.compare_and_swap(key, encoded, prev_index, ttl=ttl)
            except (ErrCASConflict, ErrKeyExists, ErrKeyNotFound):
                continue  # re-read and retry
            # desired is already private (isolated decode above)
            out = self._landed(kv, *walked)
            parts.mark(reqparts.OTHER)
            return out
        raise errors.new_conflict(obj_type.__name__, key, "too many CAS retries")

    def atomic_update_many(self, obj_type: Type,
                           updates: "list[tuple[str, Callable[[Any], Any]]]",
                           max_retries: int = 100,
                           parts=reqparts.NO_PARTS) -> list:
        """Batched read-modify-CAS over many keys — the wave-commit path
        (SURVEY §7 hard part (e)): one get_many + one compare_and_swap_many
        per round instead of two store round-trips per object. Each key is
        independent (no all-or-nothing): the result list carries, per slot,
        the updated object or the errors.StatusError that update raised /
        the key's terminal store error. CAS-conflicted slots re-read and
        retry, exactly like atomic_update, without holding back the rest.
        A round is three parts, not three an item: the read (``store``),
        every item's isolating copy, update and walk (``walk``), the swap
        and what landed (``store``).
        """
        results: list = [None] * len(updates)
        live = list(range(len(updates)))
        for _ in range(max_retries):
            if not live:
                break
            parts.mark(reqparts.STORE)
            kvs = self.store.get_many([updates[i][0] for i in live])
            parts.mark(reqparts.WALK)
            batch = []            # (slot, key, encoded, walked, prev_index)
            for i, kv in zip(live, kvs):
                key, fn = updates[i]
                if kv is None:
                    results[i] = errors.new_not_found(
                        obj_type.__name__, key.rsplit("/", 1)[-1])
                    continue
                try:
                    desired = fn(self._decode(kv, isolate=True))
                except errors.StatusError as e:
                    results[i] = e
                    continue
                encoded, walked = self._walk(desired)
                batch.append((i, key, encoded, walked, kv.modified_index))
            parts.mark(reqparts.STORE)
            outcomes = self.store.compare_and_swap_many(
                [(key, enc, prev) for _, key, enc, _, prev in batch])
            live = []
            for (i, key, _enc, walked, _prev), oc in zip(batch, outcomes):
                if isinstance(oc, ErrCASConflict):
                    live.append(i)        # lost a race: re-read and retry
                elif isinstance(oc, ErrKeyNotFound):
                    results[i] = errors.new_not_found(
                        obj_type.__name__, key.rsplit("/", 1)[-1])
                elif isinstance(oc, Exception):
                    results[i] = errors.new_internal_error(str(oc))
                else:
                    results[i] = self._landed(oc, *walked)
        parts.mark(reqparts.OTHER)
        for i in live:
            results[i] = errors.new_conflict(obj_type.__name__, updates[i][0],
                                             "too many CAS retries")
        return results

    def atomic_bind_evict_many(self, obj_type: Type,
                               items: "list[tuple]",
                               max_retries: int = 100,
                               parts=reqparts.NO_PARTS) -> list:
        """kube-preempt's commit primitive: per item, delete every victim
        AND apply the pod update in ONE store transaction (MemStore
        .txn_many) — all-or-nothing per item, items independent. Each
        item is ``(pod_key, update_fn, victims)`` with victims a list of
        ``(victim_key, expected_uid)``; a victim whose uid no longer
        matches is a 409 (the world moved — the caller must re-solve),
        while an already-absent victim counts as evicted. CAS conflicts
        re-read and retry like atomic_update_many. A round is two parts:
        its items' reads, copies and walks together (``walk``: the reads
        are an item each and not told apart), the transaction and what
        landed (``store``)."""
        results: list = [None] * len(items)
        live = list(range(len(items)))
        for _ in range(max_retries):
            if not live:
                break
            parts.mark(reqparts.WALK)
            txn = []       # (slot, cas_ops, delete_ops, walked)
            for i in live:
                pod_key, fn, victims = items[i]
                try:
                    kv = self.store.get(pod_key)
                except ErrKeyNotFound:
                    results[i] = errors.new_not_found(
                        obj_type.__name__, pod_key.rsplit("/", 1)[-1])
                    continue
                try:
                    desired = fn(self._decode(kv, isolate=True))
                except errors.StatusError as e:
                    results[i] = e
                    continue
                vkeys = [vk for vk, _uid in victims]
                vkvs = self.store.get_many(vkeys)
                deletes = []
                bad = None
                for (vk, want_uid), vkv in zip(victims, vkvs):
                    if vkv is None:
                        continue  # already gone: eviction's goal state
                    if want_uid:
                        have = accessor.uid(self._decode(vkv))
                        if have != want_uid:
                            bad = errors.new_conflict(
                                obj_type.__name__,
                                vk.rsplit("/", 1)[-1],
                                f"victim {vk.rsplit('/', 1)[-1]} uid "
                                f"changed (have {have!r}, want "
                                f"{want_uid!r}) — re-solve required")
                            break
                    deletes.append((vk, vkv.modified_index))
                if bad is not None:
                    results[i] = bad
                    continue
                encoded, walked = self._walk(desired)
                txn.append((i, [(pod_key, encoded, kv.modified_index)],
                            deletes, walked))
            if not txn:
                live = []
                break
            parts.mark(reqparts.STORE)
            outcomes = self.store.txn_many(
                [(cas, dels) for _i, cas, dels, _d in txn])
            live = []
            for (i, _cas, _dels, walked), oc in zip(txn, outcomes):
                if isinstance(oc, (ErrCASConflict, ErrKeyNotFound)):
                    live.append(i)   # raced: re-read and retry
                elif isinstance(oc, Exception):
                    results[i] = errors.new_internal_error(str(oc))
                else:
                    results[i] = self._landed(oc[0], *walked)
        parts.mark(reqparts.OTHER)
        for i in live:
            results[i] = errors.new_conflict(obj_type.__name__,
                                             items[i][0],
                                             "too many CAS retries")
        return results

    # -- watch --------------------------------------------------------------
    def watch_raw(self, prefix: str, resource_version: str = "",
                  recursive: bool = True,
                  lag_limit: Optional[int] = None) -> watchpkg.Watcher:
        """Raw StoreEvent watch — the encode-once fan-out seam. The HTTP
        layer pulls StoreEvents on its OWN connection thread and maps each
        through translate_event + the apiserver's frame-bytes cache, so
        fanning one store mutation to N watchers costs one decode + one
        encode total instead of a pump thread and a re-encode per watcher.
        ``lag_limit`` bounds the per-watcher queue (see MemStore.watch)."""
        from_index = parse_watch_resource_version(resource_version)
        try:
            return self.store.watch(prefix, from_index=from_index,
                                    recursive=recursive, lag_limit=lag_limit)
        except ErrIndexOutdated as e:
            # Surface as an API-level 410 so clients above the store boundary
            # (Reflector, HTTP clients) share one expired-watch contract.
            raise errors.new_expired(str(e))

    def translate_event_fast(self, ev: watchpkg.Event):
        """Unfiltered translate: ``(event type, resourceVersion, obj_thunk)``
        with NO decode at all — the event type falls out of the store
        action, the resourceVersion out of the store index, and the
        object is only materialized (via the shared decode cache) if the
        apiserver's frame cache actually misses. This is the observer
        fan-out fast path: a cache-hit delivery touches no codec."""
        sev = ev.object
        a = sev.action
        if a == "create":
            return (watchpkg.ADDED, str(sev.kv.modified_index),
                    lambda: self._decode(sev.kv))
        if a in ("set", "compareAndSwap"):
            t = watchpkg.MODIFIED if sev.prev_kv is not None else watchpkg.ADDED
            return (t, str(sev.kv.modified_index),
                    lambda: self._decode(sev.kv))
        if a in ("delete", "expire"):
            if sev.prev_kv is None:
                return None

            def thunk():
                prev_out = deep_clone(self._decode(sev.prev_kv))
                # deleted object carries the deletion resourceVersion
                accessor.set_resource_version(prev_out, str(sev.index))
                return prev_out

            return (watchpkg.DELETED, str(sev.index), thunk)
        return None

    def translate_event(self, ev: watchpkg.Event,
                        filter_fn: Optional[Callable[[Any], bool]] = None
                        ) -> Optional[watchpkg.Event]:
        """Map one raw store Event to its API-level watch Event, or None
        when the object is outside ``filter_fn``. Factored from the watch
        pump so the HTTP byte-writer path and the threaded pump share one
        translation (and one decode cache). Like the reference's
        etcdWatcher filter, an object transitioning out of the filter
        emits DELETED and into it emits ADDED. Raises on undecodable
        payloads — callers surface an ERROR event and keep going."""
        sev = ev.object
        cur = self._decode(sev.kv) if sev.kv else None
        prev = self._decode(sev.prev_kv) if sev.prev_kv else None
        cur_ok = cur is not None and (filter_fn is None or filter_fn(cur))
        prev_ok = prev is not None and (filter_fn is None or filter_fn(prev))
        if sev.action in ("create",):
            if cur_ok:
                return watchpkg.Event(watchpkg.ADDED, cur)
        elif sev.action in ("set", "compareAndSwap"):
            if cur_ok and prev_ok:
                return watchpkg.Event(watchpkg.MODIFIED, cur)
            if cur_ok:
                return watchpkg.Event(watchpkg.ADDED, cur)
            if prev_ok:
                # fell out of the filter: deliver the *new* state like
                # the reference (etcd_helper_watch.go sendModify)
                return watchpkg.Event(watchpkg.DELETED, cur)
        elif sev.action in ("delete", "expire"):
            if prev_ok:
                # clone: the deletion-rv rewrite below must not
                # mutate the shared cached revision
                prev_out = deep_clone(prev)
                # deleted object carries the deletion resourceVersion
                accessor.set_resource_version(prev_out, str(sev.index))
                return watchpkg.Event(watchpkg.DELETED, prev_out)
        return None

    def watch(self, prefix: str, resource_version: str = "",
              filter_fn: Optional[Callable[[Any], bool]] = None,
              recursive: bool = True,
              lag_limit: Optional[int] = None) -> watchpkg.Watcher:
        """Decoded object watch (ref: etcd_helper_watch.go:64-95 WatchList).

        Store events become ADDED/MODIFIED/DELETED watch.Events carrying API
        objects (translate_event). A bounded watcher that lags out delivers
        one ERROR Event carrying a 410 Expired Status, then ends — the
        Reflector re-lists.
        """
        src = self.watch_raw(prefix, resource_version, recursive=recursive,
                             lag_limit=lag_limit)
        out = watchpkg.Watcher(on_stop=lambda _w: src.stop())

        def pump():
            for ev in src:
                if ev.type == watchpkg.ERROR and ev.object is None:
                    # bounded-lag drop-to-resync marker from the store
                    out.send(watchpkg.Event(
                        watchpkg.ERROR,
                        errors.new_expired("watch lag bound exceeded; "
                                           "re-list required").status))
                    break
                try:
                    tev = self.translate_event(ev, filter_fn)
                except Exception as e:  # undecodable payload: surface, keep going
                    out.send(watchpkg.Event(
                        watchpkg.ERROR, errors.new_internal_error(str(e)).status))
                    continue
                if tev is not None:
                    out.send(tev)
            out.close()

        t = threading.Thread(target=pump, daemon=True, name=f"watch-{prefix}")
        t.start()
        return out
