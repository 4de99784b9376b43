"""Compiles inside the window: programs built or loaded for a shape the set-up did not warm (XLA backend
compiles; one that hit the persistent cache counts too, under ``cache_hits``).
args: key (optional: xla_compiles | cache_misses | cache_hits)."""


def read(ctx: dict, args: dict):
    compiles = ctx.get("compiles")
    if compiles is None:
        return None
    return float(compiles[args.get("key", "xla_compiles")])
