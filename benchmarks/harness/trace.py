"""From the profiler's trace to numbers: which planes are devices, the
union of the intervals in which an operation ran, the time of the events a
pattern names, the longest gaps. Works on a plain form of the trace —
{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
dur_ns], ...]}]}]} — so that the arithmetic is tested on a small recorded
trace without the profiler. ``host_spans`` are the harness's own host
annotations (``bench/<what>``), on the profiler's clock like the device's
events: [[what, start_ns, dur_ns], ...]."""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
HOST_SPAN = "bench/"
OPS_LINE = "XLA Ops"


def load_xplane(trace_dir: str, keep_plane=DEVICE_PLANE) -> dict:
    """The newest ``.xplane.pb`` under ``trace_dir`` in the plain form;
    only the planes ``keep_plane`` matches (a host plane holds hundreds of
    thousands of events nothing here reads)."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return {"planes": [], "all_planes": []}
    data = ProfileData.from_file(paths[-1])
    planes, names, spans = [], [], []
    for plane in data.planes:
        names.append(plane.name)
        if plane.name == HOST_PLANE:
            spans += [[ev.name[len(HOST_SPAN):], int(ev.start_ns),
                       int(ev.duration_ns)]
                      for line in plane.lines for ev in line.events
                      if ev.name.startswith(HOST_SPAN)]
        if not keep_plane.match(plane.name):
            continue
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                for ev in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes, "all_planes": names, "host_spans": spans}


def device_planes(trace: dict) -> list:
    return [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]


def line_events(plane: dict, line_name: str) -> list:
    return [ev for line in plane["lines"] if line["name"] == line_name
            for ev in line["events"]]


def busy_union_ns(events: list) -> int:
    """Nanoseconds covered by at least one event (overlaps count once)."""
    covered = 0
    end = None
    for _name, start, dur in sorted(events, key=lambda e: e[1]):
        stop = start + dur
        if end is None or start > end:
            covered += dur
            end = stop
        elif stop > end:
            covered += stop - end
            end = stop
    return covered


def busy_s(trace: dict, line_name: str = OPS_LINE):
    """Seconds in which an operation ran on the device, averaged over the
    device planes; None where the trace has no device plane."""
    planes = device_planes(trace)
    if not planes:
        return None
    return sum(busy_union_ns(line_events(p, line_name))
               for p in planes) / len(planes) / 1e9


def matching(trace: dict, line_name: str, pattern: str) -> list:
    rx = re.compile(pattern)
    return [ev for p in device_planes(trace)
            for ev in line_events(p, line_name) if rx.search(ev[0])]


_HLO = re.compile(r"^(%[\w.\-]+) = .*?\b([a-z][\w\-]*)\((\w+\[[\d,]*\])?")


def short_name(name: str, shape: bool = True) -> str:
    """An operation's event name is its whole HLO line (1,500 characters for
    the kernel): keep the result's name, the operation and, with ``shape``,
    the first operand's shape — ``%_solve_pallas_x32.1 custom-call
    s32[1024,40,128]``."""
    m = _HLO.match(name)
    if not m:
        return name[:80]
    parts = [m.group(1), m.group(2)] + ([m.group(3)] if shape and m.group(3)
                                        else [])
    return " ".join(parts)


def top_ops(trace: dict, line_name: str = OPS_LINE, n: int = 10) -> list:
    total: dict = {}
    for p in device_planes(trace):
        for name, _start, dur in line_events(p, line_name):
            name = short_name(name)
            total[name] = total.get(name, 0) + dur
    return [[name, ns / 1e9] for name, ns in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def _gaps(events: list) -> list:
    """[(start, stop)] of the stretches between the first and the last
    event in which none ran."""
    out, end = [], None
    for _name, start, dur in sorted(events, key=lambda e: e[1]):
        if end is not None and start > end:
            out.append((end, start))
        end = max(end or 0, start + dur)
    return out


def _overlap_ns(gaps: list, spans: list) -> int:
    """Nanoseconds of the gaps that the spans cover; both sorted, each
    disjoint in itself (one sweep over the two)."""
    total = i = 0
    for start, stop in spans:
        while i < len(gaps) and gaps[i][1] <= start:
            i += 1
        j = i
        while j < len(gaps) and gaps[j][0] < stop:
            total += min(gaps[j][1], stop) - max(gaps[j][0], start)
            j += 1
    return total


def _minus(gaps: list, spans: list) -> list:
    """The gaps with the spans cut out of them."""
    out, i = [], 0
    for g0, g1 in gaps:
        while i < len(spans) and spans[i][1] <= g0:
            i += 1
        j = i
        while j < len(spans) and spans[j][0] < g1:
            if spans[j][0] > g0:
                out.append((g0, spans[j][0]))
            g0 = max(g0, spans[j][1])
            j += 1
        if g0 < g1:
            out.append((g0, g1))
    return out


def busy_inside(trace: dict, what: str, line_name: str = OPS_LINE):
    """The share of the first device's busy time that falls inside the host
    spans named ``what``: near 1 for the solve where the two clocks agree."""
    planes = device_planes(trace)
    spans = sorted((start, start + dur) for w, start, dur in
                   trace.get("host_spans", []) if w == what)
    if not planes or not spans:
        return None
    events = sorted(line_events(planes[0], line_name), key=lambda e: e[1])
    busy = _minus([(events[0][1], max(s + d for _n, s, d in events))],
                  _gaps(events)) if events else []
    total = sum(b1 - b0 for b0, b1 in busy)
    return _overlap_ns(busy, spans) / total if total else None


def idle_gaps(trace: dict, line_name: str = OPS_LINE, n: int = 10) -> list:
    """Idle seconds on the first device by what the host was doing: first
    the interpreter's full collections (they stop every thread), then, in
    what is left, the phases of the wave loop (one thread, so they do not
    overlap); ``no span`` is idle time that none of the harness's host
    spans covers."""
    planes = device_planes(trace)
    if not planes:
        return []
    gaps = _gaps(line_events(planes[0], line_name))
    by_what: dict = {}
    for what, start, dur in sorted(trace.get("host_spans", []),
                                   key=lambda e: e[1]):
        by_what.setdefault(what, []).append((start, start + dur))
    total = {"gc": _overlap_ns(gaps, by_what.get("gc", []))}
    gaps = _minus(gaps, by_what.pop("gc", []))
    for what, spans in by_what.items():
        total[what] = _overlap_ns(gaps, spans)
    total["no span"] = sum(g1 - g0 for g0, g1 in gaps) - sum(
        ns for what, ns in total.items() if what != "gc")
    return [[what, ns / 1e9] for what, ns in
            sorted(total.items(), key=lambda kv: -kv[1])[:n] if ns > 0]


def summary(trace: dict, n: int = 12) -> dict:
    """What a hand reading wants first: planes, lines, the heaviest names."""
    out = {"all_planes": trace.get("all_planes", []), "planes": []}
    for p in trace["planes"]:
        lines = []
        for line in p["lines"]:
            total: dict = {}
            for name, _s, dur in line["events"]:
                c = total.setdefault(name, [0, 0])
                c[0] += 1
                c[1] += dur
            lines.append({"line": line["name"],
                          "events": len(line["events"]),
                          "top": [[k, c, ns / 1e9] for k, (c, ns) in sorted(
                              total.items(), key=lambda kv: -kv[1][1])[:n]]})
        out["planes"].append({"plane": p["name"], "lines": lines})
    return out
