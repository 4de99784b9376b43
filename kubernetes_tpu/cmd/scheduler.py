"""kube-scheduler binary (ref: plugin/cmd/kube-scheduler/app/server.go:74-102).

``--algorithm tpu-batch`` swaps the serial scheduleOne driver for the TPU
wave scheduler (the framework's flagship path); the default provider keeps
the serial reference semantics.
"""

from __future__ import annotations

import argparse
import signal
import sys
import threading
from typing import List, Optional

__all__ = ["scheduler_server", "main"]


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="kube-scheduler", exit_on_error=False)
    p.add_argument("--master", default="http://127.0.0.1:8080")
    p.add_argument("--algorithm-provider", "--algorithm_provider",
                   default="DefaultProvider")
    p.add_argument("--policy-config-file", "--policy_config_file", default="")
    p.add_argument("--algorithm", default="serial",
                   choices=["serial", "tpu-batch"])
    p.add_argument("--wave-period", type=float, default=0.05,
                   help="tpu-batch: max wait to accumulate a wave")
    p.add_argument("--solver-addr", "--solver_addr", default="",
                   help="tpu-batch: HOST:PORT of a shared kube-solverd "
                        "daemon (cmd/solverd). Waves solve there — many "
                        "scheduler workers share one hot solver runtime — "
                        "with automatic in-process fallback when the "
                        "daemon is absent, busy, or unhealthy. Empty = "
                        "always solve in-process.")
    p.add_argument("--solver-fallback", "--solver_fallback",
                   choices=("inprocess", "requeue"), default="inprocess",
                   help="tpu-batch with --solver-addr: what a wave does "
                        "while the daemon is away. 'inprocess' solves "
                        "locally (correct when nothing will respawn the "
                        "daemon; at full shape the cold compile can stall "
                        "the worker for minutes); 'requeue' fails the "
                        "wave — pods requeue and the next wave retries "
                        "the daemon, which a supervisor (hack/churn_mp "
                        "--chaos, docs/design/ha.md) respawns within "
                        "seconds. CAS-convergent either way.")
    p.add_argument("--mesh", choices=("auto", "on", "off"), default="auto",
                   help="tpu-batch: device-mesh solve for in-process waves "
                        "(parallel/mesh.py): auto shards waves above the "
                        "node floor over the attached device mesh when >1 "
                        "device exists (real multi-chip, or CPU sub-meshes "
                        "via XLA_FLAGS="
                        "--xla_force_host_platform_device_count=N). "
                        "Decisions stay bit-identical to the single-device "
                        "path. With --solver-addr the daemon's own --mesh "
                        "governs the shared solve; this flag still covers "
                        "the in-process fallback.")
    p.add_argument("--pods-axis", "--pods_axis", type=int, default=1,
                   help="mesh 'pods' axis length (see kube-solverd "
                        "--pods-axis)")
    p.add_argument("--prewarm", action="store_true",
                   help="kube-slipstream: at boot, compile the wave-size "
                        "bucket ladder implied by the live cluster off "
                        "the wave loop (in-process solve path only; with "
                        "--solver-addr the daemon's own --prewarm covers "
                        "the shared programs). compile_prewarm_ready on "
                        "/metrics flips to 1 when done. The fill-trigger "
                        "prewarm thread runs regardless unless "
                        "KTPU_PREWARM=off.")
    p.add_argument("--event-qps", "--event_qps", type=float, default=50.0,
                   help="client-side event rate limit (successor "
                        "codebases' --event-qps; 0 disables)")
    p.add_argument("--event-burst", "--event_burst", type=int, default=100)
    p.add_argument("--metrics-port", "--metrics_port", type=int, default=0,
                   help="serve /metrics, /healthz and /debug/pprof on this "
                        "port (0 disables; ref: the reference's healthz+"
                        "pprof mounts on every binary, master.go:431-435)")
    p.add_argument("--trace", action="store_true",
                   help="kube-trace: record spans for every wave "
                        "(drain/prepare/encode/solve/commit) into this "
                        "process's ring buffer and propagate trace context "
                        "to the apiserver and kube-solverd; drain via "
                        "GET /debug/trace on --metrics-port. Default OFF — "
                        "the disabled path is a single branch per call "
                        "site (docs/design/observability.md).")
    p.add_argument("--flightrec", action="store_true",
                   help="kube-flightrec: sample every metric series into "
                        "a per-process (monotonic_ns, value) ring from "
                        "boot, served incrementally at GET /debug/vars on "
                        "--metrics-port. Default OFF (the first "
                        "/debug/vars pull arms sampling lazily anyway).")
    p.add_argument("--flightrec-period", "--flightrec_period", type=float,
                   default=1.0,
                   help="flight recorder sample period, seconds")
    return p


def _serve_debug(port: int, service: str = "scheduler",
                 health=None) -> None:
    """Shared observability server for the non-apiserver binaries
    (scheduler, solverd): /metrics, deep /healthz (+ /healthz/ping
    liveness), /debug/pprof, /debug/trace, /debug/vars.

    ``health`` is a zero-arg callable returning componentstatus-style
    ``(payload dict, ok bool)`` — each binary probes ITS dependencies
    (scheduler: master + solverd connectivity; solverd: solver backend +
    mesh devices). None keeps the bare liveness 200."""
    import json
    import urllib.parse
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from kubernetes_tpu.util import metrics as metrics_pkg
    from kubernetes_tpu.util import pprof as pprof_util
    from kubernetes_tpu.util.metrics import default_registry

    class H(BaseHTTPRequestHandler):
        def log_message(self, *a):  # quiet
            pass

        def do_GET(self):
            ctype = "text/plain; charset=utf-8"
            if self.path.startswith("/debug/pprof"):
                parsed = urllib.parse.urlsplit(self.path)
                which = parsed.path[len("/debug/pprof"):].strip("/")
                q = dict(urllib.parse.parse_qsl(parsed.query))
                body = pprof_util.handle(which, q.get("seconds", ""),
                                         q.get("format", ""))
                code = 200 if body is not None else 404
                body = body if body is not None else "not found"
            elif self.path == "/healthz/ping":
                code, body = 200, "ok"  # liveness: process up, serving
            elif self.path.startswith("/healthz"):
                if health is None:
                    code, body = 200, "ok"
                else:
                    try:
                        payload, ok = health()
                    except Exception as e:
                        payload, ok = {"healthy": False,
                                       "error": repr(e)}, False
                    code = 200 if ok else 503
                    body, ctype = json.dumps(payload), "application/json"
            elif self.path == "/metrics":
                code, body = 200, default_registry().render_text()
            elif self.path.startswith("/debug/vars"):
                # kube-flightrec shard: incremental metric time-series
                # past the ?since=<ns> cursor; the first pull arms the
                # sampler (lazy, like the kube-trace span ring)
                q = dict(urllib.parse.parse_qsl(
                    urllib.parse.urlsplit(self.path).query))
                if not metrics_pkg.flightrec_armed():
                    metrics_pkg.flightrec_arm(service)
                try:
                    since = int(q.get("since", "0") or "0")
                except ValueError:
                    since = 0
                code = 200
                body = json.dumps(metrics_pkg.flightrec_vars(since))
                ctype = "application/json"
            elif self.path.startswith("/debug/trace"):
                # kube-trace shard drain (?peek=1 reads without resetting
                # the cursor) — the churn harness merges every process's
                # shard into one Perfetto-loadable file
                from kubernetes_tpu.util import tracing
                q = dict(urllib.parse.parse_qsl(
                    urllib.parse.urlsplit(self.path).query))
                code = 200
                body = json.dumps(tracing.drain(
                    reset=q.get("peek") not in ("1", "true")))
            else:
                code, body = 404, "not found"
            raw = body.encode()
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(raw)))
            self.end_headers()
            self.wfile.write(raw)

    srv = ThreadingHTTPServer(("127.0.0.1", port), H)
    srv.daemon_threads = True
    threading.Thread(target=srv.serve_forever, daemon=True,
                     name=f"{service}-debug-http").start()


def _scheduler_health(master: str, solver_addr: str):
    """Deep-health probe set for the scheduler binary: can it reach the
    binder (the apiserver it commits waves to) and — when configured —
    the shared solver daemon. componentstatus-style payload, non-200
    handled by the caller."""
    import urllib.parse

    from kubernetes_tpu import probe

    def health():
        items = []
        ok = True
        u = urllib.parse.urlparse(master)
        st, msg = probe.probe_http(u.hostname, u.port, "/healthz/ping")
        items.append({"name": "binder", "status": st,
                      "message": msg if st != probe.SUCCESS else
                      f"apiserver {master} reachable"})
        ok &= st == probe.SUCCESS
        if solver_addr:
            host, _, sport = solver_addr.partition(":")
            st, msg = probe.probe_tcp(host or "127.0.0.1", int(sport))
            items.append({"name": "solver", "status": st,
                          "message": msg if st != probe.SUCCESS else
                          f"kube-solverd {solver_addr} reachable"})
            # a dead daemon is DEGRADED, not down: RemoteSolver falls
            # back to in-process solves, so it does not fail liveness
        return ({"kind": "ComponentStatusList", "healthy": bool(ok),
                 "items": items}, bool(ok))

    return health


def build_scheduler(opts):
    from kubernetes_tpu.api import types as api
    from kubernetes_tpu.client.client import Client
    from kubernetes_tpu.client.http import HTTPTransport
    from kubernetes_tpu.client.record import AsyncEventRecorder, EventRecorder
    from kubernetes_tpu.scheduler import plugins as schedplugins
    from kubernetes_tpu.scheduler.driver import ConfigFactory, Scheduler

    # the user-agent is the fairshed credential: scheduler traffic
    # (reflector list/watch + the wave commit leg) rides the apiserver's
    # system flow, structurally isolated from workload create floods
    client = Client(HTTPTransport(opts.master, user_agent="kube-scheduler"))
    # async like the reference's StartRecording goroutine (event.go:53):
    # recording must never stall scheduleOne/wave loops on an API write
    recorder = AsyncEventRecorder(
        EventRecorder(client, api.EventSource(
            component=api.DefaultSchedulerName)),
        qps=getattr(opts, "event_qps", 50.0),
        burst=getattr(opts, "event_burst", 100))
    factory = ConfigFactory(client)

    policy = None
    if opts.policy_config_file:
        with open(opts.policy_config_file) as f:
            policy = schedplugins.load_policy(f.read())
    config = factory.create(provider=opts.algorithm_provider,
                            policy=policy, recorder=recorder,
                            solver_addr=getattr(opts, "solver_addr", ""),
                            mesh=getattr(opts, "mesh", "auto"),
                            pods_axis=getattr(opts, "pods_axis", 1),
                            solver_fallback=getattr(
                                opts, "solver_fallback", "inprocess"),
                            prewarm=getattr(opts, "prewarm", False))
    if opts.algorithm == "tpu-batch":
        from kubernetes_tpu.models.policy import (UnsupportedPolicy,
                                                  batch_policy_from)
        from kubernetes_tpu.scheduler.tpu_batch import BatchScheduler
        from kubernetes_tpu.util import warmstart
        # a restarted scheduler reuses compiled wave programs and router
        # calibrations instead of re-paying shape_setup_s/compile_s
        warmstart.enable()
        try:
            batch_policy = batch_policy_from(opts.algorithm_provider, policy)
        except UnsupportedPolicy as e:
            # never silently solve a different problem than configured:
            # fall back to the serial driver, which runs the plugin
            # functions directly
            print(f"kube-scheduler: tpu-batch cannot model this "
                  f"configuration ({e}); falling back to serial",
                  file=sys.stderr)
            return factory, Scheduler(config)
        return factory, BatchScheduler(config, factory, client,
                                       wave_linger_s=opts.wave_period,
                                       batch_policy=batch_policy)
    return factory, Scheduler(config)


def scheduler_server(argv: List[str],
                     ready: Optional[threading.Event] = None,
                     stop: Optional[threading.Event] = None) -> int:
    from kubernetes_tpu.util import gcpolicy, interpprobe
    gcpolicy.ensure()
    interpprobe.ensure()
    try:
        opts = build_parser().parse_args(argv)
    except argparse.ArgumentError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    if getattr(opts, "trace", False):
        from kubernetes_tpu.util import tracing
        tracing.enable("scheduler")
    if getattr(opts, "flightrec", False):
        from kubernetes_tpu.util import metrics as metrics_pkg
        metrics_pkg.flightrec_arm(
            "scheduler", period_s=getattr(opts, "flightrec_period", 1.0))
    factory, sched = build_scheduler(opts)
    if getattr(opts, "metrics_port", 0):
        _serve_debug(opts.metrics_port, service="scheduler",
                     health=_scheduler_health(
                         opts.master, getattr(opts, "solver_addr", "")))
    sched.run()
    print(f"kube-scheduler running ({opts.algorithm})", file=sys.stderr)
    if ready is not None:
        ready.set()
    stop = stop or threading.Event()
    try:
        stop.wait()
    except KeyboardInterrupt:
        pass
    sched.stop()
    factory.stop()
    return 0


def main() -> int:
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(0))
    return scheduler_server(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
