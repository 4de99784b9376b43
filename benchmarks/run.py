#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This process holds the chip: it brings up the all-in-one control plane
(harness/control_plane.py), starts the load generator as a child that
imports no JAX (feeder.py), lets it warm every pod-axis bucket through the
real path (set-up), opens a window of ``--seconds``, closes it, and decides
``correct`` (harness/correct.py) once the window has closed. Everything
that belongs to one cell is data found by name: the cell in BENCHMARK.json
names ``configs/<config>.json`` and ``traffic/<traffic>.json``, a per-layer
metric is ``metrics/<name>.json`` read by ``readers/<reader>.py``, the
plain reference is ``references/<reference>.py``. The last line of
standard output is the result's JSON object.

``--rehearse 1`` runs without a TPU (JAX_PLATFORMS=cpu, the kernel through
the interpreter) to find wrong paths; it prints counts only, under
``rehearsal``, and no metric.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse          # noqa: E402
import importlib         # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import re                # noqa: E402
import shutil            # noqa: E402
import subprocess        # noqa: E402
import sys               # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
OUT_DIR = os.path.join(ROOT, ".bench_out")
PROFILER_UP_S = 5.0      # the wave loop's stall when the profiler starts


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(bench: dict, workload: str) -> dict:
    cells = [w for w in bench["workloads"] if w["name"] == workload]
    if len(cells) != 1:
        raise SystemExit(f"run.py: no workload {workload!r} in BENCHMARK.json"
                         f" (has {[w['name'] for w in bench['workloads']]})")
    return cells[0]


def metrics_of(bench: dict, group: str, cell: dict) -> list:
    """The cell's metrics of one group, each with its own file's reader and
    args where it has a file."""
    out = []
    for m in bench[group]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        path = os.path.join(HERE, "metrics", m["name"] + ".json")
        out.append(dict(load_json(path), **m) if os.path.exists(path)
                   else dict(m))
    return out


def say(child, word: str) -> None:
    child.stdin.write(word + "\n")
    child.stdin.flush()


def tell(child, word: str, expect: str, timeout_s: float) -> str:
    """One word to the child, one line back (the parent owns the window)."""
    say(child, word)
    return hear(child, word, expect, timeout_s)


def hear(child, word: str, expect: str, timeout_s: float) -> str:
    deadline = time.monotonic() + timeout_s
    while True:
        line = child.stdout.readline()
        if line.startswith(expect):
            return line.strip()
        if not line or time.monotonic() > deadline:
            raise RuntimeError(f"the feeder answered {word!r} with "
                               f"{line!r} (rc {child.poll()})")


def device_record(jax) -> dict:
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes(jax) -> int:
    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def resident_waves(text: str) -> dict:
    """``solver_resident_waves_total`` so far, by outcome and reason: how
    often a wave's node planes were patched, and why not."""
    from benchmarks.readers import promtext
    out: dict = {}
    for name, labels, value in promtext.parse(text):
        if name == "solver_resident_waves_total" and value:
            key = "/".join(labels[k] for k in ("outcome", "reason")
                           if labels.get(k))
            out[key] = out.get(key, 0) + int(value)
    return out


def run(args) -> int:
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = find_cell(bench, args.workload)
    config_entry = [c for c in bench["configs"]
                    if c["name"] == cell["config"]][0]
    config = load_json(ROOT, config_entry["file"])
    traffic_path = os.path.join(HERE, "traffic", cell["traffic"] + ".json")
    traffic = load_json(traffic_path)
    config_path = os.path.join(ROOT, config_entry["file"])
    overrides = {}
    for target, path, pairs in (("config", config_path, args.config_set),
                                ("traffic", traffic_path, args.traffic_set)):
        if not pairs:
            continue
        doc = config if target == "config" else traffic
        for pair in pairs:
            key, _, value = pair.partition("=")
            doc[key] = json.loads(value)
            overrides[f"{target}.{key}"] = doc[key]
        os.makedirs(OUT_DIR, exist_ok=True)
        path = os.path.join(OUT_DIR, f"override-{os.path.basename(path)}")
        with open(path, "w") as f:
            json.dump(doc, f)
        if target == "config":
            config_path = path
        else:
            traffic_path = path
    from benchmarks.harness import deployment as dep
    dep.scheduler(config)      # a plugin it does not know ends it here
    for key, value in config.get("env", {}).items():
        os.environ[key] = str(value)           # pinned, not defaulted
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ.setdefault("KTPU_PALLAS", "interpret")

    import jax
    device = device_record(jax)
    if not args.rehearse and (device["platform"] != "tpu"
                              or device["count"] < int(cell["chips"])):
        print(f"run.py: {args.workload} needs {cell['chips']} TPU chip(s); "
              f"JAX reports {device}; nothing was run", file=sys.stderr)
        return 1

    from kubernetes_tpu.util import warmstart
    warmstart.enable()
    from benchmarks.harness import control_plane as cpl
    from benchmarks.harness import correct as cor
    from benchmarks.harness import trace as tr

    out_dir = os.path.join(OUT_DIR, args.workload)
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    feeder_out = os.path.join(out_dir, "feeder.json")
    trace_dir = os.path.join(out_dir, "trace")

    clog = cpl.CompileLog()
    annotation = jax.profiler.TraceAnnotation if args.trace else None
    gclog = cpl.GcLog(annotation)
    t_imported = time.monotonic()
    plane = cpl.ControlPlane(config, args.seed, clog)
    if args.trace:
        plane.annotate_phases(annotation)
    t_up = time.monotonic()
    child = None
    try:
        with open(os.path.join(out_dir, "feeder.log"), "w") as log:
            child = subprocess.Popen(
                [sys.executable, os.path.join(HERE, "feeder.py"),
                 "--base-url", plane.srv.base_url,
                 "--config", config_path,
                 "--traffic", traffic_path, "--seed", str(args.seed),
                 "--out", feeder_out,
                 "--max-seconds", str(args.seconds + 5.0)],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=log,
                text=True, bufsize=1)
        for n in traffic["warm_rounds"]:
            # one round, one wave: the round is created behind a shut
            # gate and let through whole, so that the wave loop meets
            # every pod-axis bucket before the window and not inside it
            with plane.gate_shut():
                tell(child, f"warm {int(n)}", "created", 300.0)
                cpl.wait_for(lambda: plane.queued() >= int(n), 120.0,
                             f"the scheduler's queue to hold {n} pods")
            hear(child, "warm", "warmed", 1150.0)
        t_warmed = time.monotonic()
        cpl.wait_for(plane.prewarm_idle, 900.0,
                     "the scheduler's prewarm thread to finish its queue")
        warm_waves = len(plane.waves)
        before_text = plane.metrics_text()
        programs0 = cpl.program_counts()
        setup_compiles = clog.since((0, 0, 0))
        mark = clog.mark()

        opened = tell(child, "open", "opened", 30.0)
        open_t = float(opened.split()[1])
        setup_s = open_t - T_START
        traced = None
        if args.trace:
            # the last seconds of the window, so that writing the trace
            # out (seconds of host work) falls after the close
            span = min(float(traffic.get("trace_s", 5.0)) + PROFILER_UP_S,
                       args.seconds * 0.8)
            time.sleep(max(0.0, open_t + args.seconds - span
                           - time.monotonic()))
            # the Python tracer (on by default) follows every call of
            # every thread: it slows the host it is meant to observe and
            # swells the trace until the device plane is dropped
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            # the device takes some seconds to answer again once the
            # profiler is on; the span starts with the first wave after
            seen = len(plane.waves)
            while len(plane.waves) == seen and \
                    time.monotonic() < open_t + args.seconds - 1.0:
                time.sleep(0.01)
            t0 = time.monotonic()
        time.sleep(max(0.0, open_t + args.seconds - time.monotonic()))
        say(child, "close")
        if args.trace:
            traced = (t0, time.monotonic())
            jax.profiler.stop_trace()
        hear(child, "close", "done", 150.0)
        child.stdin.close()
        child.wait(timeout=30.0)

        after_text = plane.metrics_text()
        compiles = clog.since(mark)
        programs = {k: n - programs0.get(k, 0)
                    for k, n in cpl.program_counts().items()
                    if n - programs0.get(k, 0)}
        device["memory_peak_bytes"] = memory_peak_bytes(jax)
        pods, nodes = plane.final_lists()
        events = dict(plane.recorder.by_reason)
        waves = list(plane.waves)
    finally:
        if child is not None and child.poll() is None:
            child.kill()
            child.wait()
        plane.stop()
        gclog.close()

    # -- the window has closed, the peak is read, the program is stopped ----
    feeder_doc = load_json(feeder_out)
    listed = cpl.check_final_list(pods, nodes)
    del pods, nodes
    kernel = f"{config['kernel_program']}@{device['platform']}"
    t_ref = time.monotonic()
    verdict = cor.compare(config, feeder_doc, waves, listed, programs,
                          events, kernel)
    numbers = verdict["numbers"]
    if args.control:
        numbers["control.decisions_differ"] = (
            cor.control_reading(config, feeder_doc, waves), None)
    reference_s = time.monotonic() - t_ref
    correct = cor.is_correct({k: v for k, v in numbers.items()
                              if v[1] is not None})

    summary = feeder_doc["summary"]
    ctx = {"metrics_before": before_text, "metrics_after": after_text,
           "compiles": compiles, "feeder": feeder_doc, "trace": None,
           "gc_pauses": gclog.between(feeder_doc["open_t"],
                                      feeder_doc["close_t"]),
           "device_kind": device["kind"], "window_s": summary["window_s"]}
    breakdown = None
    if traced is not None:
        trace = tr.load_xplane(trace_dir)
        if args.dump_trace:          # every plane, for a reading by hand
            with open(args.dump_trace, "w") as f:
                json.dump(tr.summary(tr.load_xplane(
                    trace_dir, keep_plane=re.compile(""))), f, indent=1)
        ctx["trace"] = trace
        ctx["traced_s"] = traced[1] - traced[0]
        ctx["traced_waves"] = [w for w in waves
                               if traced[0] <= w["t"] <= traced[1]]
        busy = tr.busy_s(trace)
        if busy is not None:
            device["busy_s"] = busy
            device["window_s"] = ctx["traced_s"]
            breakdown = {"device_ops": tr.top_ops(trace),
                         "idle_gaps": tr.idle_gaps(trace)}
            # the two clocks agree where the device works inside the solve
            ctx.setdefault("notes", {})["busy_inside_solve_span"] = \
                tr.busy_inside(trace, "solve")
        shutil.rmtree(trace_dir, ignore_errors=True)

    values = {}
    if args.trace:
        for m in metrics_of(bench, "per_layer", cell):
            reader = importlib.import_module(
                f"benchmarks.readers.{m['reader']}")
            v = reader.read(ctx, m.get("args", {}))
            if v is not None:
                values[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        measured = dict(summary, setup_s=setup_s)
        for m in metrics_of(bench, "end_to_end", cell):
            values[m["name"]] = {"value": measured[m["name"]],
                                 "unit": m["unit"]}

    compared = {k: {"value": v, "limit": lim}
                for k, (v, lim) in numbers.items()}
    resident0 = resident_waves(before_text)
    resident = {"setup": resident0,
                "window": {k: n - resident0.get(k, 0) for k, n in
                           resident_waves(after_text).items()
                           if n - resident0.get(k, 0)}}
    in_window = [w["t"] for w in waves
                 if feeder_doc["open_t"] <= w["t"] <= feeder_doc["close_t"]]
    edges = [feeder_doc["open_t"]] + in_window + [feeder_doc["close_t"]]
    gap, gap_at = max((b - a, a - feeder_doc["open_t"])
                      for a, b in zip(edges, edges[1:]))
    side = {"waves": len(waves), "warm_waves": warm_waves,
            "max_wave_gap_s": gap, "max_wave_gap_at_s": gap_at,
            "window_waves": len(waves) - warm_waves,
            "compared_pods": verdict["compared_pods"],
            "first_diff": verdict["first_diff"], "programs": programs,
            "setup_programs": programs0, "resident": resident,
            "compiles": compiles, "setup_compiles": setup_compiles,
            "reference_s": reference_s,
            "gc_pauses_s": [[round(began - feeder_doc["open_t"], 3),
                             round(secs, 4)] for began, secs in gclog.pauses
                            if feeder_doc["open_t"] <= began
                            <= feeder_doc["close_t"]],
            "setup_s": setup_s,
            "setup_parts_s": {"imports": t_imported - T_START,
                              "control_plane": t_up - t_imported,
                              "warm_rounds": t_warmed - t_up,
                              "prewarm_and_open": open_t - t_warmed},
            "drain_s": feeder_doc["drain_s"],
            "register_nodes_s": plane.register_nodes_s,
            "watch_relists": feeder_doc["watch_relists"],
            "summary": summary, "notes": ctx.get("notes", {}),
            "overrides": overrides}
    print("run.py: " + json.dumps(side), file=sys.stderr)
    for name, (value, limit) in numbers.items():
        print(f"compared {name} = {value} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()

    if args.rehearse:
        result = {"rehearsal": True, "correct": correct,
                  "attempted": summary["attempted"],
                  "failed": summary["failed"], "metrics": {},
                  "device": device, "names": sorted(values),
                  "compared": compared}
    else:
        result = {"correct": correct, "attempted": summary["attempted"],
                  "failed": summary["failed"], "metrics": values,
                  "device": device}
        if breakdown is not None:
            result["breakdown"] = breakdown
        if overrides:
            result["overrides"] = overrides
        result["compared"] = compared
    print(json.dumps(result), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, choices=(0, 1), default=0,
                    help="run without a TPU; prints counts and no metric")
    ap.add_argument("--config-set", action="append", metavar="KEY=JSON",
                    help="for rehearsals and sweeps, never the driver: one "
                         "key of the configuration replaced (nodes=50)")
    ap.add_argument("--traffic-set", action="append", metavar="KEY=JSON",
                    help="likewise for the traffic mix (rate=200); the "
                         "result says so under 'overrides'")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the control (the reference with the "
                         "in-wave commit put off) over the same waves")
    ap.add_argument("--dump-trace", default="",
                    help="write a summary of the device trace to this file")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "kubernetes_tpu")):
        print("run.py: no kubernetes_tpu/ beside benchmarks/: there is no "
              "system to measure here", file=sys.stderr)
        return 1
    return run(args)


if __name__ == "__main__":
    sys.exit(main())
