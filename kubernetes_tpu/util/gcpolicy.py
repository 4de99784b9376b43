"""Old objects leave the collector's walk.

A control-plane process holds the cluster as trees of API objects: a pod is
some sixty collector-tracked containers across the store and the reflector
caches, reference counts free every one of them, and CPython still walks
the whole heap whenever the old generation has grown by a quarter — with
every thread stopped. ``ensure()`` moves what survived a full collection to
the permanent generation (``gc.freeze()``), so the next full collection
walks what was promoted since, not the cluster. Frozen objects that later
become *cyclic* garbage are found by a whole walk (``gc.unfreeze()`` before
a full collection) once every ``WHOLE_WALK_PERIOD_S``. Generations 0 and 1
and every threshold stay as the interpreter has them.

The hook runs inside the collector, in whatever thread tripped it, possibly
under one of the registry's locks: it takes no lock and only adds to its
own accumulators; a render-time collector copies them into the series
``process_gc_pause_seconds{generation}``, ``process_gc_pause_seconds_total``,
``process_gc_collected_total{generation}``, ``process_gc_whole_walks_total``
and ``process_gc_frozen_objects``.
"""

from __future__ import annotations

import gc
import threading
import time
from bisect import bisect_left

from kubernetes_tpu.util import metrics

__all__ = ["ensure", "reset", "WHOLE_WALK_PERIOD_S"]

# One long pause a period in place of one per quarter of growth. What the
# whole walks find (process_gc_collected_total{generation="2"}) is what
# justifies the number: PERF.md §6, PR 28.
WHOLE_WALK_PERIOD_S = 600.0

_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
            0.5, 1.0, 2.5)
_FULL = 2                       # the oldest generation
_clock = time.monotonic         # tests put their own here

_install_lock = threading.Lock()
_installed = False
_began = None                   # perf_counter at the running collection's start
_whole = False                  # the running collection walks the frozen too
_last_whole = 0.0               # _clock() at ensure() or at the last whole walk
# by generation, for the life of the process; collections never nest, so
# the hook is their only writer: per-bucket counts, sum of seconds
_pauses = [[[0] * (len(_BUCKETS) + 1), 0.0] for _ in range(_FULL + 1)]
_collected = [0] * (_FULL + 1)
_whole_walks = 0
_frozen_as_of = -1              # full collections at the frozen gauge's last read


def _on_gc(phase: str, info: dict) -> None:
    global _began, _whole, _last_whole, _whole_walks
    generation = info["generation"]
    if phase == "start":
        _began = time.perf_counter()
        if generation == _FULL and \
                _clock() - _last_whole >= WHOLE_WALK_PERIOD_S:
            gc.unfreeze()
            _whole = True
        return
    if _began is None:          # installed while this collection ran
        return
    if generation == _FULL:
        if _whole:
            _whole = False
            _whole_walks += 1
            _last_whole = _clock()
        gc.freeze()
    seconds = time.perf_counter() - _began
    _began = None
    series = _pauses[generation]
    series[0][bisect_left(_BUCKETS, seconds)] += 1
    series[1] += seconds
    _collected[generation] += info["collected"]


def ensure() -> None:
    """Install the policy in this process; a second call does nothing."""
    global _installed, _last_whole, _frozen_as_of
    with _install_lock:
        if _installed:
            return
        _installed = True
        gc.collect()
        gc.freeze()             # imports, registries: never walked again
        _frozen_as_of = -1
        _last_whole = _clock()
        gc.callbacks.append(_on_gc)


def reset() -> None:
    """Tests only: take the hook out and hand the frozen back."""
    global _installed, _began, _whole, _frozen_as_of
    with _install_lock:
        if _installed:
            gc.callbacks.remove(_on_gc)
        gc.unfreeze()
        _installed, _began, _whole, _frozen_as_of = False, None, False, -1


_reg = metrics.default_registry()
_PAUSE = _reg.histogram(
    "process_gc_pause_seconds",
    "Seconds every thread of this process stood still for one collection",
    ("generation",), buckets=_BUCKETS)
_PAUSE_TOTAL = _reg.counter(
    "process_gc_pause_seconds_total",
    "Seconds this process stood still for collections of any generation")
_COLLECTED = _reg.counter(
    "process_gc_collected_total",
    "Objects the collections of a generation freed (reference counts "
    "free the rest)", ("generation",))
_WHOLE_WALKS = _reg.counter(
    "process_gc_whole_walks_total",
    "Full collections that walked the frozen objects too")
_FROZEN = _reg.gauge(
    "process_gc_frozen_objects",
    "Objects in the permanent generation, which no collection walks")


_collect_lock = threading.Lock()     # renders; the hook never takes it


def _collect() -> None:
    """Render-time collector: bring the five series up to the hook's
    accumulators."""
    global _frozen_as_of
    with _collect_lock:
        for generation, (counts, seconds) in enumerate(_pauses):
            _PAUSE.load(counts, seconds, generation)
            _COLLECTED.inc(generation, by=max(
                0, _collected[generation] - _COLLECTED.value(generation)))
        paused = sum(seconds for _, seconds in _pauses)
        _PAUSE_TOTAL.inc(by=max(0.0, paused - _PAUSE_TOTAL.value()))
        _WHOLE_WALKS.inc(by=max(0, _whole_walks - _WHOLE_WALKS.value()))
        # counting the frozen walks their list (15 ms a million, every
        # thread waiting), and only a full collection adds to it
        fulls = sum(_pauses[_FULL][0])
        if fulls != _frozen_as_of:
            _frozen_as_of = fulls
            _FROZEN.set(gc.get_freeze_count())


_reg.add_collector(_collect)
