"""Ratio of two counters' growth over the window (a share with scale 100).
args: numerator {series, labels}, denominator {series, labels}, scale."""

from benchmarks.readers import promtext


def read(ctx: dict, args: dict):
    num, den = args["numerator"], args["denominator"]
    d = promtext.delta(ctx, den["series"], den.get("labels", {}))
    if not d:
        return None
    n = promtext.delta(ctx, num["series"], num.get("labels", {}))
    return n / d * float(args.get("scale", 1.0))
