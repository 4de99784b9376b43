"""The kernel's share of its roofline where the wave's pods have services,
in percent: ``readers/roofline.py`` with ``benchmarks/roofline_groups.py``'s
count in place of ``roofline.py``'s. The group rows a wave carried are not
among the sizes the harness records of a wave, so they are read from the
program's own counter: the growth of ``groups`` over the growth of ``waves``
in the window, a mean. Finds nothing to read — and returns nothing, never 0
— where the trace has no such event, no wave fell in the span, the program
keeps no such counter (the parent), or no wave named a group.
args: line, pattern, groups {series, labels}, waves {series, labels}."""

from benchmarks import roofline, roofline_groups
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
    per_wave = [roofline_groups.least_seconds(
        dict(w["dims"], G=groups / n_waves), peaks) for w in waves]
    mean_least = sum(t for t, _ in per_wave) / len(per_wave)
    kernel_s = sum(dur for _n, _s, dur in events) / 1e9
    ctx.setdefault("notes", {})["roofline_groups"] = {
        "groups_a_wave": groups / n_waves,
        "bound": max(set(b for _, b in per_wave),
                     key=[b for _, b in per_wave].count)}
    return 100.0 * mean_least * len(events) / kernel_s
