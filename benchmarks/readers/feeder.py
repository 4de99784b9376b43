"""A number of the load generator's own summary. args: key, scale."""


def read(ctx: dict, args: dict):
    value = ctx["feeder"]["summary"].get(args["key"])
    if value is None:
        return None
    return float(value) * float(args.get("scale", 1.0))
