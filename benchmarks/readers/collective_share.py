"""The share of the device's busy time spent in collective operations, in
percent: on each device plane the union of the intervals of the events the
pattern names (an operation waiting for its peers counts: that is what a
collective costs) over the union of all events of the line, averaged over
the planes. Finds nothing to read — and returns nothing, never 0 — where
the trace has no device plane, the device never ran, or no event is a
collective (one chip, a program with none).
args: line (optional), pattern (regex on the event name; the default names
the HLO operations all-reduce, all-gather, collective-permute, all-to-all
and reduce-scatter, with their -start and -done halves)."""

import re

from benchmarks.harness import trace as tr

COLLECTIVE = (r"\b(all-reduce|all-gather|collective-permute|all-to-all|"
              r"reduce-scatter)(-start|-done)?\(")


def read(ctx: dict, args: dict):
    if ctx.get("trace") is None:
        return None
    rx = re.compile(args.get("pattern", COLLECTIVE))
    line = args.get("line", tr.OPS_LINE)
    shares = []
    for plane in tr.device_planes(ctx["trace"]):
        events = tr.line_events(plane, line)
        busy = tr.busy_union_ns(events)
        if busy:
            shares.append(tr.busy_union_ns(
                [ev for ev in events if rx.search(ev[0])]) / busy)
    if not any(shares):
        return None
    return 100.0 * sum(shares) / len(shares)
