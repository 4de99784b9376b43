#!/usr/bin/env python3
"""Several runs of one cell, one after the other, each a process of its own
(this one never touches JAX), and the spread of each metric over them: the
distance between the first and third quartile as a share of the median,
by ``statistics.quantiles(values, n=4)``. Every result line and the
harness's own side line go to ``--out`` (a .jsonl under chiprun_out/).

    python3 benchmarks/tools/runs.py --workload W --seeds 11 12 13 --sets 2
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def spread(values: list):
    if len(values) < 2:
        return None
    q = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q[2] - q[0]) / med if med else None


def one_run(workload, seed, seconds, trace, extra) -> dict:
    cmd = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
           "--workload", workload, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace)] + extra
    t0 = time.monotonic()
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    rec = {"workload": workload, "seed": seed, "trace": trace,
           "rc": p.returncode, "wall_s": time.monotonic() - t0}
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    try:
        rec["result"] = json.loads(lines[-1])
    except (IndexError, ValueError):
        rec["stdout_tail"] = p.stdout[-2000:]
    side = [ln for ln in p.stderr.splitlines() if ln.startswith("run.py: {")]
    if side:
        rec["side"] = json.loads(side[-1][len("run.py: "):])
    if p.returncode or "result" not in rec:
        rec["stderr_tail"] = p.stderr[-4000:]
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default="")
    ap.add_argument("extra", nargs="*", help="after --: passed to run.py")
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = args.seconds or json.load(f)["run_seconds"]
    out = args.out or os.path.join(
        ROOT, "chiprun_out", f"runs-{args.workload}.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    sets = []
    for s in range(args.sets):
        recs = []
        for seed in args.seeds:
            rec = one_run(args.workload, seed, seconds, args.trace,
                          args.extra)
            rec["set"] = s
            recs.append(rec)
            with open(out, "a") as f:
                f.write(json.dumps(rec) + "\n")
            res = rec.get("result", {})
            print(json.dumps({
                "set": s, "seed": seed, "rc": rec["rc"],
                "wall_s": round(rec["wall_s"], 1),
                "correct": res.get("correct"),
                "attempted": res.get("attempted"),
                "failed": res.get("failed"),
                "metrics": {k: v["value"] for k, v in
                            res.get("metrics", {}).items()},
                "device": res.get("device"),
                "side": {k: rec.get("side", {}).get(k) for k in
                         ("window_waves", "compiles", "setup_compiles",
                          "reference_s",
                          "drain_s", "watch_relists", "programs",
                          "setup_programs", "resident",
                          "notes", "max_wave_gap_s", "max_wave_gap_at_s",
                          "gc_pauses_s",
                          "setup_parts_s", "summary")},
                "bad": {k: v for k, v in res.get("compared", {}).items()
                        if v["limit"] is None or v["value"] > v["limit"]},
                "err": rec.get("stderr_tail", "")[-1500:]}), flush=True)
        sets.append(recs)
    names = sorted({k for recs in sets for r in recs
                    for k in r.get("result", {}).get("metrics", {})})
    for name in names:
        row = {"metric": name}
        for s, recs in enumerate(sets):
            vals = [r["result"]["metrics"][name]["value"] for r in recs
                    if name in r.get("result", {}).get("metrics", {})]
            # a side's first run compiles or loads: set-up is judged apart
            if name == "setup_s" and s == 0:
                vals = vals[1:]
            if vals:
                row[f"set{s}"] = {"n": len(vals),
                                  "median": statistics.median(vals),
                                  "min": min(vals), "max": max(vals),
                                  "spread": spread(vals)}
        print("spread " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
