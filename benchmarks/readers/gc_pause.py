"""The interpreter's full collections inside the window (``GcLog`` of the
harness, in the process that serves): ``key`` "max" is the longest one,
"total" their sum, "count" how many; milliseconds with ``scale`` 1000.
A window with none reads 0: that is a reading, not a gap.
args: key, scale (optional)."""


def read(ctx: dict, args: dict):
    pauses = ctx.get("gc_pauses")
    if pauses is None:
        return None
    key = args["key"]
    if key == "count":
        return float(len(pauses))
    value = max(pauses, default=0.0) if key == "max" else sum(pauses)
    return value * float(args.get("scale", 1.0))
