"""The device's idle share of the traced span, in percent: 1 - union of the
intervals in which an operation ran over the span. args: line (optional)."""

from benchmarks.harness import trace as tr


def read(ctx: dict, args: dict):
    if ctx.get("trace") is None or not ctx.get("traced_s"):
        return None
    busy = tr.busy_s(ctx["trace"], args.get("line", tr.OPS_LINE))
    if busy is None:
        return None
    return 100.0 * (1.0 - busy / ctx["traced_s"])
