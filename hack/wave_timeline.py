#!/usr/bin/env python3
"""One wave of a traced benchmark run, read by hand: the program's
``ktpu/`` phases, the harness's ``bench/`` spans and the device's events of
that wave on one clock, as text.

    python3 hack/wave_timeline.py --workload <cell> --seed <n> --seconds 51 \
        --out chiprun_out/timeline.txt

Runs ``benchmarks/run.py --trace 1 --dump-trace`` in this process (it needs
the chip) and hooks the harness's trace summary, which is handed every plane
of the profiler's trace, to write the timeline of the middle wave of the
traced span beside it; ``<out>.series.json`` gets the window's growth of
every series the phase helper and the role marks write (the harness keeps
only the metrics made of them). Changes nothing under benchmarks/.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def timeline(trace: dict) -> str:
    host = [p for p in trace["planes"] if p["name"] == "/host:CPU"]
    dev = [p for p in trace["planes"] if p["name"].startswith("/device:TPU")]
    # every thread's line is named alike ("python"): tell them apart by
    # their place in the plane
    events = [(name, start, dur, f"{line['name']}#{i}") for p in host
              for i, line in enumerate(p["lines"])
              for name, start, dur in line["events"]
              if name.startswith(("ktpu/", "bench/"))]
    solves = sorted(e for e in events if e[0] == "bench/solve")
    if not solves:
        return "no bench/solve span in the trace\n"
    _n, s0, sdur, loop_line = solves[len(solves) // 2]
    # the wave: from the drain before this solve to the commit after it
    on_loop = sorted((e for e in events if e[3] == loop_line),
                     key=lambda e: e[1])
    lo = max((e[1] for e in on_loop
              if e[0] == "bench/drain" and e[1] <= s0), default=s0)
    hi = min((e[1] + e[2] for e in on_loop
              if e[0] == "bench/commit" and e[1] >= s0), default=s0 + sdur)
    out = [f"wave loop thread: {loop_line}; times in ms from the start of "
           f"bench/solve; {len(solves)} waves in the trace"]
    for name, start, dur, _line in on_loop:
        if lo <= start and start + dur <= hi:
            out.append(f"{(start - s0) / 1e6:10.3f} +{dur / 1e6:9.3f}  "
                       f"host    {name}")
    for p in dev[:1]:
        for line in p["lines"]:
            for name, start, dur in line["events"]:
                if lo <= start <= hi and line["name"] == "XLA Ops":
                    out.append(f"{(start - s0) / 1e6:10.3f} +{dur / 1e6:9.3f}"
                               f"  device  {name[:60]}")
    other = collections.Counter()
    for name, start, dur, line in events:
        if line != loop_line and start < s0 + sdur and start + dur > s0:
            other[name] += 1
    n_ktpu = sum(1 for e in events if e[0].startswith("ktpu/"))
    out.append(f"ktpu/ events in the trace: {n_ktpu} on "
               f"{len({e[3] for e in events})} threads")
    out.append("spans of other threads that overlap this bench/solve: "
               + (", ".join(f"{n} x{c}" for n, c in other.most_common(12))
                  or "none"))
    out[1:-2] = sorted(out[1:-2], key=lambda ln: float(ln.split()[0]))
    return "\n".join(out) + "\n"


SERIES = ("scheduler_wave_", "scheduler_queue_wait_seconds_sum",
          "scheduler_queue_wait_seconds_count", "process_role_cpu_",
          "process_wall_", "apiserver_batch_bind_seconds",
          "apiserver_request_latencies_seconds", "pod_watch_observe_seconds")


def series_growth(before: str, after: str) -> dict:
    """Window growth of the helper's series, buckets left out."""
    from benchmarks.readers import promtext
    was = {(n, tuple(sorted(lab.items()))): v
           for n, lab, v in promtext.parse(before)}
    grown = {}
    for n, lab, v in promtext.parse(after):
        if n.startswith(SERIES) and not n.endswith("_bucket"):
            key = n + "".join(f"{{{k}={val}}}" for k, val in sorted(
                lab.items()))
            grown[key] = v - was.get((n, tuple(sorted(lab.items()))), 0.0)
    return grown


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--untraced", action="store_true",
                    help="a --trace 0 run: the series' growth only")
    args, rest = ap.parse_known_args(argv)
    from benchmarks import run as bench_run
    from benchmarks.harness import trace as tr

    inner = tr.summary

    def summary(trace, n=12):
        with open(args.out, "w") as f:
            f.write(timeline(trace))
        return inner(trace, n)

    tr.summary = summary
    from benchmarks.harness import control_plane as cpl
    texts = []
    render = cpl.ControlPlane.metrics_text

    def metrics_text(plane):
        texts.append(render(plane))     # the window's two ends, in order
        return texts[-1]

    cpl.ControlPlane.metrics_text = metrics_text
    rc = bench_run.main(rest + (["--trace", "0"] if args.untraced else [
        "--trace", "1", "--dump-trace", args.out + ".planes.json"]))
    if len(texts) >= 2:
        with open(args.out + ".series.json", "w") as f:
            json.dump(series_growth(texts[0], texts[-1]), f, indent=1)
    return rc


if __name__ == "__main__":
    sys.exit(main())
