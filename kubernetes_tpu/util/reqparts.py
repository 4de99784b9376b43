"""One request's wall time, by part.

The apiserver's handler makes a ``RequestParts`` when a request's first
line has arrived (the part is ``read``) and calls ``mark(part)`` at each
boundary it crosses: one clock reading, and the time since the last mark
is added to the part that ends there. A part lasts until the next mark:
marks do not nest, so whoever ends a part names what follows it (``OTHER``
where nothing does). Boundaries in other modules are marked through the
registry ``Context``, which carries the request's object down
(``NO_PARTS`` for callers that are no HTTP request). When the request ends
its parts go, in one step, into a ``PartTotals``, which a registry renders
as ``apiserver_request_part_seconds_total{verb,resource,group,part}`` and
``apiserver_request_offcpu_seconds_total{verb,resource}`` (wall less the
handler thread's own CPU over the same stretch).

These are wall parts: each holds its share of the waits for the
interpreter lock, and ``read`` and ``send`` hold the socket's. The CPU
between two requests of a connection (the base class's loop, some
microseconds) is booked to the later one. Not through
``tracing.phase``, which costs more a phase than a whole request may here
(PERF.md §6, PR 27).
"""

from __future__ import annotations

import threading
from time import perf_counter_ns, thread_time_ns

__all__ = ["RequestParts", "PartTotals", "NO_PARTS", "PARTS", "READ",
           "DECODE", "ADMIT", "VALIDATE", "WALK", "STORE", "ENCODE", "SEND",
           "OTHER"]

# (part, group): a metric file names a group and gets its parts together
PARTS = (("read", "http"),       # request line, headers, URL, body
         ("decode", "codec"),    # the body through the scheme
         ("admit", "rules"),     # authorization and the admission chain
         ("validate", "rules"),  # the registry's defaults and validation
         ("walk", "codec"),      # StoreHelper._walk: encode and typed copy
         ("store", "store"),     # the store's record, its fan-out, _landed
         ("encode", "codec"),    # the response (and a bind's frame seeds)
         ("send", "http"),       # headers and body onto the socket
         ("other", "other"))
READ, DECODE, ADMIT, VALIDATE, WALK, STORE, ENCODE, SEND, OTHER = \
    range(len(PARTS))


class RequestParts:
    __slots__ = ("ns", "part", "t", "t0", "cpu0")

    def __init__(self, cpu_ns=None):
        """``cpu_ns``: the thread's CPU clock as the last request of this
        connection left it (what ``end`` returned). A thread that waits
        for its next request uses no CPU, so that reading is this one's
        start — and the clock is a system call that holds the interpreter
        (5.6 us on the benchmark's host, seventy-five readings of the
        other clock): a connection's first request takes it twice, every
        later one once."""
        self.ns = [0] * len(PARTS)
        self.part = READ
        self.t = self.t0 = perf_counter_ns()
        self.cpu0 = thread_time_ns() if cpu_ns is None else cpu_ns

    def mark(self, part: int) -> None:
        """The running part ends here and ``part`` begins."""
        now = perf_counter_ns()
        self.ns[self.part] += now - self.t
        self.t = now
        self.part = part

    def end(self, totals: "PartTotals", verb: str, resource: str) -> int:
        """The request is over: its parts and its off-CPU time go into
        ``totals``. On the thread that made this object; returns that
        thread's CPU clock, for the connection's next request. The off-CPU
        time of one request may come out negative where the CPU clock
        ticks coarser than a request lasts (10 ms on that host): it is
        added as it is, since only the sum means anything."""
        # the CPU clock first: it is the one system call here, and where
        # the kernel takes a thread off its core at the next system call
        # (lazy preemption) that wait belongs inside the last part
        cpu_ns = thread_time_ns()
        self.mark(OTHER)
        totals.add((verb, resource), self.ns,
                   self.t - self.t0 - (cpu_ns - self.cpu0))
        return cpu_ns


class _NoParts:
    """What a ``Context`` carries when no HTTP request stands behind it."""

    __slots__ = ()

    def mark(self, part: int) -> None:
        pass


NO_PARTS = _NoParts()


class PartTotals:
    """Nanoseconds by (verb, resource) and part, and the two counters a
    render brings up to them."""

    def __init__(self, registry):
        self._lock = threading.Lock()
        self._rows: dict = {}   # (verb, resource) -> ns by part + [off-CPU]
        self._part_seconds = registry.counter(
            "apiserver_request_part_seconds_total",
            "Wall seconds of requests by the part of the handler that "
            "spent them (the parts of a verb and resource sum to its "
            "requests' wall)", ("verb", "resource", "group", "part"))
        self._offcpu_seconds = registry.counter(
            "apiserver_request_offcpu_seconds_total",
            "Seconds requests were open without their thread running: "
            "wall less the handler thread's own CPU time",
            ("verb", "resource"))
        registry.add_collector(self._collect)

    def add(self, key: tuple, ns: list, offcpu_ns: int) -> None:
        with self._lock:
            row = self._rows.get(key)
            if row is None:
                row = self._rows[key] = [0] * (len(PARTS) + 1)
            for i, spent in enumerate(ns):
                row[i] += spent
            row[-1] += offcpu_ns

    def _collect(self) -> None:
        with self._lock:
            for (verb, resource), row in self._rows.items():
                for (part, group), ns in zip(PARTS, row):
                    labels = (verb, resource, group, part)
                    self._part_seconds.inc(*labels, by=max(
                        0.0, ns * 1e-9 - self._part_seconds.value(*labels)))
                self._offcpu_seconds.inc(verb, resource, by=max(
                    0.0, row[-1] * 1e-9
                    - self._offcpu_seconds.value(verb, resource)))
