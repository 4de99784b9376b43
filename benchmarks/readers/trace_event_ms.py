"""Device time of the events a pattern names, in milliseconds for each
event (one launch a wave): summed durations over the count.
args: line (the device plane's line), pattern (regex on the event name)."""

from benchmarks.harness import trace as tr


def read(ctx: dict, args: dict):
    if ctx.get("trace") is None:
        return None
    events = tr.matching(ctx["trace"], args.get("line", tr.OPS_LINE),
                         args["pattern"])
    if not events:
        return None
    return sum(dur for _n, _s, dur in events) / len(events) / 1e6
