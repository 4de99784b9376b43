"""kube-trace — low-overhead distributed tracing for the control plane.

Every wall this repo broke (r07 bind cost, r08 solve p50, r09 reshard
bytes) was found by hand-stitching per-process counters into a timeline
after the fact. This module makes the timeline a first-class artifact:
each process keeps a bounded in-memory ring of completed spans, span
context propagates across every process boundary the stack already has
(the delta-wire ``trace`` header field, the ``X-KTPU-Trace`` HTTP
header), and ``GET /debug/trace`` drains the ring so the churn harness
can merge all shards into one Chrome-trace-event / Perfetto-loadable
JSON file per run (Dapper's model: causal spans, sampled at the edges,
collected out-of-band).

Design constraints, in order:

1. **Disabled tracing must be free.** Production entrypoints default
   tracing OFF; the scheduler's encode/solve/commit stage loop calls
   into this module per wave, so the off path is one module-global load
   and a branch (``span()`` returns a shared no-op object; nothing is
   allocated beyond the kwargs dict the call site built). The overhead
   guard in ``tests/test_tracing.py`` pins this at <1% of the stage
   loop.
2. **Recording never blocks.** The ring is a preallocated slot array
   indexed by an ``itertools.count`` (its ``next`` is one atomic C
   call under the GIL, the same lock-free-in-CPython idiom the watch
   fan-out counters use): writers claim a slot index and store one
   fully-built record with a single list assignment — no lock, no
   resize, no back-pressure. When writers outrun the drain the oldest
   slots are overwritten and the loss is COUNTED (``dropped``), never
   hidden and never a stall.
3. **Clocks merge across processes.** Span times are
   ``time.monotonic_ns()``, which on Linux is CLOCK_MONOTONIC — one
   clock per host, shared by every process — so spans from the
   apiserver, scheduler workers, and solverd land on one comparable
   axis without wall-clock smearing. (Cross-host merging would need an
   offset handshake; the multi-process topology is single-host today.)

Span context is ``(trace_id, span_id)``. Ambient context is a
per-thread stack (``span()`` nests); crossing a thread or process
boundary is explicit: ``current()``/``wire()`` capture the context,
``parent=``/``parse()`` re-attach it. A span with no parent starts a
new trace.

Wire forms:

- HTTP: ``X-KTPU-Trace: <trace_id>-<span_id>`` (request header; watch
  streams echo the stream's context back as a response header).
- kube-solverd frames (protocol v3): ``"trace": [trace_id, span_id]``
  in the solve header. v1/v2 clients simply omit it and are served
  untraced.

**One helper, three sinks.** ``phase()`` is the one instrumentation
point of a timed phase (the wave loop's stages and their parts): from ONE
pair of clock readings it feeds the always-on histogram and the off-CPU
counter, enters a ``jax.profiler.TraceAnnotation`` named ``ktpu/<name>``
(inert without a profiler session; with one, the span lies on the device
events' clock), and records the kube-trace span when tracing is on. This
module never imports JAX: the annotation class is found through
``sys.modules``, so a process that never loaded JAX writes none.
``role()`` marks a thread's role once; a render-time collector turns the
marks into ``process_role_cpu_seconds_total{role}``.

Span taxonomy, wire encodings, and the merge pipeline are documented in
docs/design/observability.md.
"""

from __future__ import annotations

import itertools
import json
import os
import sys
import threading
import time
import uuid
from typing import Any, Dict, Iterable, List, Optional, Tuple

from kubernetes_tpu.util import metrics

__all__ = ["HEADER", "ANNOTATION_PREFIX", "enabled", "enable", "disable",
           "span", "child_span", "phase", "clocks", "record", "current",
           "new_ctx", "wire", "parse", "role", "role_end", "role_cpu_seconds",
           "drain", "loss_peek", "chrome_trace", "NOP"]

HEADER = "X-KTPU-Trace"

# module-global fast-path flag: `span()` and friends read this before
# touching any state, so disabled tracing costs one load + one branch
_on = False

_DEFAULT_CAPACITY = 65536


class _Ring:
    """Preallocated slot array; see module docstring point 2. Each slot
    holds ``(seq, record)`` so the drain can tell live entries from
    overwritten history without a writer-side lock."""

    def __init__(self, capacity: int):
        self.cap = int(capacity)
        self.slots: List[Optional[tuple]] = [None] * self.cap
        self._seq = itertools.count()
        self._drain_lock = threading.Lock()
        self._drained_through = 0  # seq below which spans were returned

    def put(self, rec: dict) -> None:
        i = next(self._seq)          # atomic claim
        self.slots[i % self.cap] = (i, rec)

    def drain(self, reset: bool = True) -> Tuple[List[dict], int, int]:
        """-> (spans in seq order, written_total, dropped). ``dropped``
        counts spans overwritten before any drain saw them. Concurrent
        writers keep writing; a racing slot may carry a span newer than
        the snapshot — it is simply returned (and not returned again)."""
        with self._drain_lock:
            lo = self._drained_through
            live = [s for s in self.slots if s is not None and s[0] >= lo]
            live.sort(key=lambda s: s[0])
            written = (live[-1][0] + 1) if live else lo
            dropped = (written - lo) - len(live)
            if reset:
                self._drained_through = written
            return [rec for _i, rec in live], written, dropped


class _State:
    __slots__ = ("service", "ring")

    def __init__(self):
        self.service = ""
        # allocated by enable(): a process that never traces (the
        # default everywhere) must not pay for the slot array at import
        self.ring: Optional[_Ring] = None


_state = _State()
_tls = threading.local()
_span_seq = itertools.count(1)
_PID_TAG = ""  # refreshed on enable(): fork-safe span-id uniqueness


def _ctx_stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def _new_trace_id() -> str:
    return uuid.uuid4().hex[:16]


def _new_span_id() -> str:
    return f"{_PID_TAG}{next(_span_seq):x}"


def enabled() -> bool:
    return _on


def enable(service: str = "", capacity: int = _DEFAULT_CAPACITY) -> None:
    """Turn tracing on for this process. ``service`` names the process
    in merged traces (apiserver / scheduler / solverd / ...);
    ``capacity`` bounds the span ring (oldest spans evicted past it)."""
    global _on, _PID_TAG
    _PID_TAG = f"{os.getpid():x}."
    _state.service = service or _state.service
    if _state.ring is None or _state.ring.cap != capacity:
        _state.ring = _Ring(capacity)
    _on = True


def disable() -> None:
    global _on
    _on = False


# -- context ----------------------------------------------------------------

def current() -> Optional[Tuple[str, str]]:
    """The ambient (trace_id, span_id), or None outside any span (or
    with tracing off)."""
    if not _on:
        return None
    st = getattr(_tls, "stack", None)
    return st[-1] if st else None


def new_ctx() -> Optional[Tuple[str, str]]:
    """A fresh root context for a trace whose spans share no enclosing
    span (a wave's phases): no span is recorded for the root itself —
    stages attach to it with ``parent=ctx`` and the merged view groups
    them by trace id."""
    if not _on:
        return None
    return (_new_trace_id(), _new_span_id())


def wire(ctx: Optional[Tuple[str, str]] = None) -> str:
    """``trace_id-span_id`` for the X-KTPU-Trace header ('' when no
    context is active)."""
    c = ctx if ctx is not None else current()
    return f"{c[0]}-{c[1]}" if c else ""


def parse(value) -> Optional[Tuple[str, str]]:
    """Inverse of ``wire``; tolerant of junk (returns None)."""
    if not value or not isinstance(value, str):
        return None
    tid, sep, sid = value.partition("-")
    if not sep or not tid or not sid or len(tid) > 64 or len(sid) > 64:
        return None
    return (tid, sid)


# -- spans ------------------------------------------------------------------

class _NopSpan:
    """Shared do-nothing span: the disabled fast path and the parent of
    no one. Supports the full surface so call sites never branch."""

    __slots__ = ()
    ctx = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


NOP = _NopSpan()

_AMBIENT = object()  # sentinel: "use the thread's current span as parent"


class _Span:
    __slots__ = ("name", "attrs", "ctx", "psid", "_t0", "_pushed")

    def __init__(self, name: str, parent, attrs: dict):
        self.name = name
        self.attrs = attrs
        if parent is _AMBIENT:
            parent = current()
        if parent:
            tid, psid = parent
        else:
            tid, psid = _new_trace_id(), ""
        self.ctx = (tid, _new_span_id())
        self.psid = psid
        self._t0 = 0
        self._pushed = False

    def push(self):
        _ctx_stack().append(self.ctx)
        self._pushed = True

    def __enter__(self):
        self.push()
        self._t0 = time.monotonic_ns()
        return self

    def set(self, **attrs):
        self.attrs.update(attrs)
        return self

    def close(self, t0, end, exc_type, emit=True):
        if self._pushed:
            st = _ctx_stack()
            if st and st[-1] == self.ctx:
                st.pop()
            self._pushed = False
        if exc_type is not None:
            self.attrs.setdefault("error", exc_type.__name__)
        if emit:
            _emit(self.name, self.ctx, self.psid, t0, end, self.attrs)

    def __exit__(self, exc_type, exc, tb):
        self.close(self._t0, time.monotonic_ns(), exc_type)
        return False


def span(name: str, parent=_AMBIENT, **attrs):
    """Context manager for one span. ``parent`` defaults to the thread's
    ambient span; pass an explicit ``(trace_id, span_id)`` (or None for
    a new root) when crossing threads. Free when tracing is off."""
    if not _on:
        return NOP
    return _Span(name, parent, attrs)


def child_span(name: str, **attrs):
    """``span()`` that records ONLY under an active ambient trace: a
    no-op when tracing is off OR when the thread is outside any span.
    For shared internals on both traced and untraced paths (registry
    writes: a traced bind's store leg should appear in the wave's trace,
    but 50k untraced feeder creates must not each open a root trace and
    churn the ring)."""
    if not _on:
        return NOP
    st = getattr(_tls, "stack", None)
    if not st:
        return NOP
    return _Span(name, st[-1], attrs)


# -- phases: one instrumentation point, three sinks ---------------------------

ANNOTATION_PREFIX = "ktpu/"

_OFFCPU = metrics.default_registry().counter(
    "scheduler_wave_offcpu_seconds_total",
    "Seconds a thread held a phase open without running: wall less the "
    "thread's own CPU time (waiting for the device, a socket, a condition "
    "or the interpreter lock)", ("span",))

# jax.profiler.TraceAnnotation, once JAX has been loaded by somebody else
_annotation = None


def _find_annotation():
    """The profiler's annotation class if this process has loaded JAX, else
    None (asked again next time: JAX may be imported later, or be halfway
    through its import on another thread right now)."""
    global _annotation
    cls = getattr(sys.modules.get("jax.profiler"), "TraceAnnotation", None)
    if cls is not None:
        _annotation = cls
    return cls


_CPU_REUSE_NS = 20_000


def _thread_cpu_ns(now_ns: int) -> int:
    """The calling thread's CPU clock at ``now_ns``, a monotonic reading
    taken just before. The clock is a system call, and on some hosts a slow
    one (5.8 us on the benchmark's, sixty monotonic readings). Phases
    follow and nest in one another within microseconds, so a reading
    younger than 20 us is carried forward as if the thread had run since:
    in so short a gap it cannot have waited for long. Only fresh readings
    are kept, so carried ones never chain."""
    last = getattr(_tls, "cpu", None)
    if last is not None and 0 <= now_ns - last[0] < _CPU_REUSE_NS:
        return last[1] + now_ns - last[0]
    cpu = time.thread_time_ns()
    _tls.cpu = (now_ns, cpu)
    return cpu


def clocks() -> Tuple[int, int]:
    """``(monotonic_ns, thread CPU ns)`` now: a phase's ``since=`` when its
    start lies before the ``with`` block (a wait carried over empty ticks)."""
    now = time.monotonic_ns()
    return now, _thread_cpu_ns(now)


class _Phase:
    __slots__ = ("name", "hist", "label", "wall_s", "_span", "_ann", "_t0",
                 "_c0", "_live")

    def __init__(self, name, hist, label, span, ann, since):
        self.name = name
        self.hist = hist
        self.label = label
        self._span = span
        self._ann = ann
        self._live = True
        self.wall_s = 0.0            # set on exit
        self._t0, self._c0 = since if since is not None else (0, 0)

    @property
    def ctx(self):
        return self._span.ctx if self._span is not None else None

    def set(self, **attrs):
        if self._span is not None:
            self._span.attrs.update(attrs)
        return self

    def cancel(self):
        """Feed neither the histogram nor kube-trace on exit (an empty
        tick of a polling loop is not an occurrence of the phase)."""
        self._live = False
        return self

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        if self._span is not None:
            self._span.push()
        if not self._t0:
            self._t0 = time.monotonic_ns()
            if self.hist is not None:
                self._c0 = _thread_cpu_ns(self._t0)
        return self

    def __exit__(self, exc_type, exc, tb):
        end = time.monotonic_ns()
        span = self._span
        if span is not None:
            span.close(self._t0, end, exc_type, self._live)
        wall = end - self._t0
        self.wall_s = wall * 1e-9
        if self._live and self.hist is not None:
            # a start taken on another thread (``since``) has another CPU
            # clock: the clamp keeps 0 <= offcpu <= wall whatever it read
            off = min(max(wall - (_thread_cpu_ns(end) - self._c0), 0), wall)
            if self.label is None:
                self.hist.observe(wall * 1e-9)
            else:
                self.hist.observe(wall * 1e-9, self.label)
            _OFFCPU.inc(self.name, by=off * 1e-9)
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        return False


def phase(name: str, hist=None, label: Optional[str] = None, *,
          parent=_AMBIENT, traced: bool = True, since=None, detail: str = "",
          **attrs):
    """Context manager round one timed phase; use it as a ``with`` block at
    the site (a wrapper function would put one more frame on the wave
    loop's stack). On exit, from one pair of clock readings:

    1. always, when ``hist`` is given: ``hist.observe(wall[, label])`` and
       ``wall - thread CPU`` onto ``scheduler_wave_offcpu_seconds_total
       {span=name}``;
    2. always: a ``jax.profiler.TraceAnnotation`` named ``ktpu/<name>``
       (``ktpu/<name>.<detail>`` with ``detail``) — inert without a
       profiler session, absent in a process that never loaded JAX;
    3. with kube-trace on and ``traced``: the span ``name`` under
       ``parent`` — an explicit context, None for a new root, or by default
       the thread's ambient span and NO span where there is none (shared
       code also runs off any traced path). The span is ambient inside the
       block, as ``span()``'s is.

    ``since`` (from ``clocks()``) moves the start of 1 and 3 back."""
    ann = _annotation or _find_annotation()
    if ann is not None:
        ann = ann(ANNOTATION_PREFIX + name + "." + detail if detail
                  else ANNOTATION_PREFIX + name)
    sp = None
    if _on and traced:
        if parent is _AMBIENT:
            parent = current()
            if parent is not None:
                sp = _Span(name, parent, attrs)
        else:
            sp = _Span(name, parent, attrs)
    return _Phase(name, hist, label, sp, ann, since)


def record(name: str, start_ns: int, end_ns: int, parent=None,
           **attrs) -> None:
    """Retroactive completed span — for sites that know a span's bounds
    only after the fact (the solverd gather/solve loop times a batch,
    then attributes it to each wave's trace)."""
    if not _on:
        return
    if parent is _AMBIENT:
        parent = current()
    if parent:
        tid, psid = parent
    else:
        tid, psid = _new_trace_id(), ""
    _emit(name, (tid, _new_span_id()), psid, start_ns, end_ns, attrs)


def _emit(name, ctx, psid, t0, end, attrs) -> None:
    _state.ring.put({
        "name": name, "tid": ctx[0], "sid": ctx[1], "psid": psid,
        "t0": t0, "dur": max(0, end - t0),
        "thr": threading.current_thread().name,
        "attrs": attrs,
    })


# -- interpreter time by thread role -----------------------------------------
# A thread marks its role once, where the role begins; nothing runs on any
# hot path after that. The collector (when a registry renders) reads every
# marked thread's CPU clock. CPU seconds are not lock-held seconds — C code
# runs without the interpreter lock — but over a window they say who used
# the one interpreter while another thread waited.

ROLES = ("wave_loop", "http", "watch_send", "reflector")

_roles_lock = threading.Lock()
# thread ident -> (role, the thread's CPU clock id, cpu_ns at the mark)
_roles: Dict[int, tuple] = {}
# role -> cpu_ns of threads that ended or re-marked
_banked: Dict[str, int] = {}
_T_IMPORT = time.monotonic()


def role(name: Optional[str]) -> None:
    """The calling thread runs as ``name`` from here on (None: as nothing).
    Its CPU so far is banked to the role it had."""
    now = time.thread_time_ns()
    ident = threading.get_ident()
    with _roles_lock:
        prev = _roles.pop(ident, None)
        if prev is not None:
            _banked[prev[0]] = _banked.get(prev[0], 0) + now - prev[2]
        if name is not None:
            # the clock id is taken by the thread itself, while it surely
            # lives: reading it for a thread that ended fails cleanly
            _roles[ident] = (name, time.pthread_getcpuclockid(ident), now)


def role_end() -> None:
    """The calling thread's role ends (its ``finally``): bank its CPU."""
    role(None)


def role_cpu_seconds() -> Dict[str, float]:
    """CPU seconds by role so far — banked plus every live marked thread's
    clock — with ``other``: the process's CPU less all of them."""
    live = {t.ident for t in threading.enumerate()}
    with _roles_lock:
        total = dict(_banked)
        for ident, (name, clock_id, c0) in list(_roles.items()):
            try:
                if ident not in live:
                    raise OSError    # its clock id may be another's by now
                total[name] = total.get(name, 0) + \
                    time.clock_gettime_ns(clock_id) - c0
            except OSError:
                # ended without role_end(): its last stretch is lost
                del _roles[ident]
        process = time.process_time_ns()
    out = dict.fromkeys(ROLES, 0.0)
    out.update((name, ns * 1e-9) for name, ns in total.items())
    out["other"] = max(0.0, process * 1e-9 - sum(out.values()))
    return out


_ROLE_CPU = metrics.default_registry().counter(
    "process_role_cpu_seconds_total",
    "CPU seconds of this process by the role its threads marked "
    "(other: process CPU less the marked threads)", ("role",))
_WALL = metrics.default_registry().counter(
    "process_wall_seconds_total",
    "Wall seconds since this process loaded its tracing module")
_collect_lock = threading.Lock()


def _collect_roles() -> None:
    """Render-time collector: bring the two counters up to now."""
    with _collect_lock:
        for name, seconds in role_cpu_seconds().items():
            _ROLE_CPU.inc(name, by=max(0.0, seconds - _ROLE_CPU.value(name)))
        _WALL.inc(by=time.monotonic() - _T_IMPORT - _WALL.value())


metrics.default_registry().add_collector(_collect_roles)


# -- collection -------------------------------------------------------------

def loss_peek() -> Optional[int]:
    """Unread-span loss estimate WITHOUT draining: spans evicted since
    the last drain (the flight recorder samples this once per second as
    the ``tracing_spans_dropped`` gauge feeding the spans-dropped SLO).
    None when tracing was never enabled — the sampler then records no
    series rather than a fake healthy zero."""
    ring = _state.ring
    if ring is None:
        return None
    with ring._drain_lock:
        lo = ring._drained_through
        live = hi = 0
        for s in ring.slots:
            if s is not None and s[0] >= lo:
                live += 1
                if s[0] >= hi:
                    hi = s[0] + 1
        return max(0, (hi - lo) - live)


def drain(reset: bool = True) -> Dict[str, Any]:
    """The ``GET /debug/trace`` payload: this process's span shard.
    Draining resets the ring's read position (each span is returned
    once); ``dropped`` counts spans evicted unread since the previous
    drain."""
    if _state.ring is None:  # tracing never enabled in this process
        spans, written, dropped = [], 0, 0
    else:
        spans, written, dropped = _state.ring.drain(reset=reset)
    return {"service": _state.service or f"pid{os.getpid()}",
            "pid": os.getpid(), "spans": spans,
            "written": written, "dropped": dropped}


def chrome_trace(shards: Iterable[Dict[str, Any]]) -> Dict[str, Any]:
    """Merge drained shards (one per process) into one Chrome-trace-
    event JSON object (Perfetto's legacy JSON importer loads it as-is:
    ui.perfetto.dev -> Open trace file). Spans become complete events
    ('ph': 'X', microsecond timestamps on the shared monotonic axis);
    process/thread names come from metadata events, and every event
    carries its trace/span ids in ``args`` so a trace id typed into the
    Perfetto search box lights up one pod-wave's causal path across
    every process."""
    events: List[dict] = []
    for shard in shards:
        pid = int(shard.get("pid", 0))
        svc = shard.get("service") or f"pid{pid}"
        events.append({"ph": "M", "name": "process_name", "pid": pid,
                       "tid": 0, "args": {"name": svc}})
        tids: Dict[str, int] = {}
        for sp in shard.get("spans", ()):
            thr = sp.get("thr", "")
            tid = tids.get(thr)
            if tid is None:
                tid = tids[thr] = len(tids) + 1
                events.append({"ph": "M", "name": "thread_name",
                               "pid": pid, "tid": tid,
                               "args": {"name": thr}})
            args = dict(sp.get("attrs") or ())
            args["trace_id"] = sp.get("tid", "")
            args["span_id"] = sp.get("sid", "")
            if sp.get("psid"):
                args["parent_span_id"] = sp["psid"]
            events.append({
                "ph": "X", "cat": "ktpu", "name": sp.get("name", "?"),
                "pid": pid, "tid": tid,
                "ts": sp.get("t0", 0) / 1000.0,
                "dur": max(1, sp.get("dur", 0)) / 1000.0,
                "args": args,
            })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def dump_chrome(shards: Iterable[Dict[str, Any]], path: str) -> str:
    """chrome_trace -> file; returns ``path`` (the churn harness's
    per-run artifact)."""
    with open(path, "w") as fh:
        json.dump(chrome_trace(shards), fh)
    return path
