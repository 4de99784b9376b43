"""A kernel's share of its roofline, in percent: the least time the chip
could take for the waves solved inside the traced span
(``benchmarks/roofline.py``, peaks by ``device_kind``) over the device time
of the kernel's events in that span. Finds nothing to read — and returns
nothing, never 0 — where the trace has no such event or no wave fell in the
span. args: line, pattern."""

from benchmarks import roofline
from benchmarks.harness import trace as tr


def read(ctx: dict, args: dict):
    if ctx.get("trace") is None:
        return None
    events = tr.matching(ctx["trace"], args.get("line", tr.OPS_LINE),
                         args["pattern"])
    waves = ctx.get("traced_waves") or []
    if not events or not waves:
        return None
    peaks = roofline.peaks_for(ctx["device_kind"])
    # one launch a wave: the launches seen and the waves recorded in the
    # span differ by at most the one the span's edge cut, so the least
    # time is taken for as many waves as launches were seen
    per_wave = [roofline.least_seconds(w["dims"], peaks) for w in waves]
    mean_least = sum(t for t, _ in per_wave) / len(per_wave)
    kernel_s = sum(dur for _n, _s, dur in events) / 1e9
    ctx.setdefault("notes", {})["roofline_bound"] = max(
        set(b for _, b in per_wave), key=[b for _, b in per_wave].count)
    return 100.0 * mean_least * len(events) / kernel_s
