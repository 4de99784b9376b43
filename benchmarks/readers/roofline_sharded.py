"""A sharded program's share of its roofline, in percent, where the node
axis is divided over the chips: the least time ONE chip could take for its
share of the waves solved inside the traced span — ``benchmarks/roofline.py``
for the wave's sizes with the node planes and the cells divided by the
number of chips, the pod rows and the outputs whole (every chip reads and
writes them) — over the device time of the program's events, each chip's
own. The ``roofline`` reader would hold every chip to the whole wave and
read as many times too high as there are chips. The chips are the device
planes that hold a matching event. Finds nothing to read — and returns
nothing, never 0 — where the trace has no such event or no wave fell in the
span. args: line, pattern."""

import re

from benchmarks import roofline
from benchmarks.harness import trace as tr


def read(ctx: dict, args: dict):
    if ctx.get("trace") is None:
        return None
    rx = re.compile(args["pattern"])
    line = args.get("line", tr.OPS_LINE)
    per_plane = [[ev for ev in tr.line_events(p, line) if rx.search(ev[0])]
                 for p in tr.device_planes(ctx["trace"])]
    per_plane = [events for events in per_plane if events]
    waves = ctx.get("traced_waves") or []
    if not per_plane or not waves:
        return None
    chips = len(per_plane)
    peaks = roofline.peaks_for(ctx["device_kind"])
    # one launch a wave and chip; the launches seen and the waves recorded
    # differ by at most the one the span's edge cut (see readers/roofline)
    least = [roofline.least_seconds(
        dict(w["dims"], N=-(-int(w["dims"]["N"]) // chips)), peaks)[0]
        for w in waves]
    launches = sum(len(events) for events in per_plane)
    device_s = sum(dur for events in per_plane
                   for _n, _s, dur in events) / 1e9
    ctx.setdefault("notes", {})["roofline_sharded_chips"] = chips
    return 100.0 * sum(least) / len(least) * launches / device_s
