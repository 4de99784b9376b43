"""The kernel's share of its roofline under a Policy with ServiceAffinity and
ServiceAntiAffinity, in percent: ``readers/roofline_groups.py`` with
``benchmarks/roofline_policy.py``'s count. The group rows a wave carried
come from the program's own counters, as there (a mean over the window);
the policy's sizes ``L``, ``A`` and ``V`` are the configuration's, stated in
the metric's args. Finds nothing to read — and returns nothing, never 0 —
where the trace has no such event, no wave fell in the span, the program
keeps no such counter, or no wave named a group.
args: line, pattern, groups {series, labels}, waves {series, labels}, L, A,
V."""

from benchmarks import roofline, roofline_policy
from benchmarks.harness import trace as tr
from benchmarks.readers import promtext


def read(ctx: dict, args: dict):
    if ctx.get("trace") is None:
        return None
    events = tr.matching(ctx["trace"], args.get("line", tr.OPS_LINE),
                         args["pattern"])
    waves = ctx.get("traced_waves") or []
    n_waves = promtext.delta(ctx, args["waves"]["series"],
                             args["waves"].get("labels", {}))
    groups = promtext.delta(ctx, args["groups"]["series"],
                            args["groups"].get("labels", {}))
    if not events or not waves or not n_waves or not groups:
        return None
    peaks = roofline.peaks_for(ctx["device_kind"])
    sizes = {k: args[k] for k in ("L", "A", "V")}
    per_wave = [roofline_policy.least_seconds(
        dict(w["dims"], G=groups / n_waves, **sizes), peaks) for w in waves]
    mean_least = sum(t for t, _ in per_wave) / len(per_wave)
    kernel_s = sum(dur for _n, _s, dur in events) / 1e9
    ctx.setdefault("notes", {})["roofline_policy"] = {
        "groups_a_wave": groups / n_waves,
        "bound": max(set(b for _, b in per_wave),
                     key=[b for _, b in per_wave].count)}
    return 100.0 * mean_least * len(events) / kernel_s
