"""Mean of a histogram over the window: delta of ``_sum`` over delta of
``_count`` between two ``render()`` texts. (``Histogram.quantile()``
returns bucket upper bounds and is used for nothing.)
args: series, labels (optional, all must match), scale (optional)."""

from benchmarks.readers import promtext


def read(ctx: dict, args: dict):
    labels = args.get("labels", {})
    dsum = promtext.delta(ctx, args["series"] + "_sum", labels)
    dcount = promtext.delta(ctx, args["series"] + "_count", labels)
    if not dcount:
        return None
    return dsum / dcount * float(args.get("scale", 1.0))
