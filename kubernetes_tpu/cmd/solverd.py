"""kube-solverd binary — the shared batch-solver daemon.

The reference has no analog: its scheduler is a per-pod loop with no
accelerator to share. In this rebuild the solver runtime (JAX + compiled
wave programs) is the one component that must NOT be replicated per
scheduler worker — one hot daemon serves them all (see
docs/design/solver.md and kubernetes_tpu/solver/service.py).

Usage: python -m kubernetes_tpu.cmd.solverd [--port 10450]
           [--gather-window 0.003] [--max-batch 16] [--max-queue 64]
           [--metrics-port 0]
"""

from __future__ import annotations

import argparse
import signal
import sys
import threading
from typing import List, Optional

__all__ = ["solverd_server", "main"]

DEFAULT_PORT = 10450


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="kube-solverd", exit_on_error=False)
    p.add_argument("--address", default="127.0.0.1")
    p.add_argument("--port", type=int, default=DEFAULT_PORT)
    p.add_argument("--gather-window", "--gather_window", type=float,
                   default=0.003,
                   help="seconds to gather concurrent waves into one "
                        "batched solve (wave coalescing)")
    p.add_argument("--max-batch", "--max_batch", type=int, default=16,
                   help="max waves per batched device call")
    p.add_argument("--max-queue", "--max_queue", type=int, default=64,
                   help="bounded request queue; beyond this, requests get "
                        "an immediate BUSY reply (backpressure) instead of "
                        "unbounded latency")
    p.add_argument("--cache-entries", "--cache_entries", type=int,
                   default=64,
                   help="delta-wire resident plane cache entries (one per "
                        "worker thread x shape bucket); evictions cost the "
                        "evicted client one full-frame resync")
    p.add_argument("--metrics-port", "--metrics_port", type=int, default=0,
                   help="serve /metrics, /healthz and /debug/pprof on this "
                        "port (0 disables)")
    p.add_argument("--mesh", choices=("auto", "on", "off"), default="auto",
                   help="device-mesh production dispatch "
                        "(solver/mesh_exec.py): auto enables it whenever "
                        ">1 device is attached — real multi-chip, or CPU "
                        "sub-meshes via XLA_FLAGS="
                        "--xla_force_host_platform_device_count=N; waves "
                        "above --mesh-min-nodes then solve from "
                        "device-resident sharded planes")
    p.add_argument("--pods-axis", "--pods_axis", type=int, default=1,
                   help="mesh 'pods' axis length; the rest of the devices "
                        "shard the node axis (pods_axis=1 is pure "
                        "tensor-parallel over nodes)")
    p.add_argument("--mesh-min-nodes", "--mesh_min_nodes", type=int,
                   default=None,
                   help="node-count floor for the mesh dispatch (default "
                        "parallel.mesh.DEFAULT_MESH_MIN_NODES); smaller "
                        "waves keep the padded vmap path")
    p.add_argument("--mesh-dispatch", "--mesh_dispatch",
                   choices=("auto", "shard", "single"), default="auto",
                   help="node-axis layout: auto times the fully-sharded "
                        "scan against the single-device submesh once per "
                        "shape (persisted in the warm-start dir) and runs "
                        "the winner; shard/single pin a layout")
    p.add_argument("--mesh-probe", "--mesh_probe",
                   choices=("first", "all", "off"), default="first",
                   help="live bit-identity probe: re-solve mesh-path "
                        "waves in the other layout and compare bitwise "
                        "(first = once per daemon run)")
    p.add_argument("--prewarm", action="store_true",
                   help="kube-slipstream: compile the shape-bucket set "
                        "implied by --prewarm-nodes/-pods/-batch at boot, "
                        "off the solve path, before the first request; "
                        "compile_prewarm_ready flips to 1 on /metrics "
                        "when done (the churn harness gates its load "
                        "window on it). The fill-trigger prewarm thread "
                        "runs regardless unless KTPU_PREWARM=off.")
    p.add_argument("--prewarm-nodes", "--prewarm_nodes", type=int,
                   default=0,
                   help="declared cluster node count for the boot "
                        "prewarm set (pow-2 rounded)")
    p.add_argument("--prewarm-pods", "--prewarm_pods", type=int,
                   default=1024,
                   help="top of the pod-axis bucket ladder to prewarm "
                        "(ladder descends to 256)")
    p.add_argument("--prewarm-batch", "--prewarm_batch", type=int,
                   default=1,
                   help="vmap batch axis to prewarm in addition to 1 "
                        "(set to the expected concurrent-worker count)")
    p.add_argument("--trace", action="store_true",
                   help="kube-trace: record queue-wait + solve spans, "
                        "attached to the requesting wave's trace when the "
                        "v3 frame carries one; drain via GET /debug/trace "
                        "on --metrics-port. Default OFF.")
    p.add_argument("--flightrec", action="store_true",
                   help="kube-flightrec: sample every metric series into "
                        "a per-process (monotonic_ns, value) ring from "
                        "boot, served incrementally at GET /debug/vars on "
                        "--metrics-port. Default OFF (the first "
                        "/debug/vars pull arms sampling lazily anyway).")
    p.add_argument("--flightrec-period", "--flightrec_period", type=float,
                   default=1.0,
                   help="flight recorder sample period, seconds")
    p.add_argument("--trace-device", "--trace_device", default="",
                   help="directory for a jax.profiler device trace of the "
                        "daemon's solves (open in Perfetto/TensorBoard "
                        "alongside the kube-trace host spans). Empty "
                        "disables. Orthogonal to --trace: this is XLA's "
                        "own profiler, started at daemon boot and stopped "
                        "on shutdown.")
    return p


def _solverd_health(srv):
    """Deep-health probe set for the daemon: the solver backend (a JAX
    runtime that lost its devices cannot serve waves) and — when the
    mesh dispatch is on — the device mesh itself. componentstatus-style
    payload; the metrics-port server answers 503 when unhealthy."""
    from kubernetes_tpu import probe

    def health():
        items = []
        ok = True
        try:
            import jax
            n = jax.device_count()
            backend = jax.default_backend()
            st = probe.SUCCESS if n >= 1 else probe.FAILURE
            items.append({"name": "backend", "status": st,
                          "message": f"{backend}, {n} device(s)"})
            ok &= st == probe.SUCCESS
        except Exception as e:
            items.append({"name": "backend", "status": probe.FAILURE,
                          "message": repr(e)})
            ok = False
        me = getattr(srv, "_mesh_exec", None)
        if me is not None:
            shards = getattr(me, "node_shards", 0)
            st = probe.SUCCESS if shards >= 1 else probe.FAILURE
            items.append({"name": "mesh", "status": st,
                          "message": f"{shards} node-shard(s) x "
                                     f"{getattr(me, 'pods_axis', 1)} pods"})
            ok &= st == probe.SUCCESS
        return ({"kind": "ComponentStatusList", "healthy": bool(ok),
                 "items": items}, bool(ok))

    return health


def solverd_server(argv: List[str],
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

    from kubernetes_tpu.solver.service import SolverService
    from kubernetes_tpu.util import warmstart
    # the daemon owns the hottest solver runtime in the topology: reuse
    # compiled wave programs + router calibrations across restarts
    warmstart.enable()
    if opts.trace:
        from kubernetes_tpu.util import tracing
        tracing.enable("solverd")
    device_trace = None
    if opts.trace_device:
        # XLA's own device profiler rides alongside the kube-trace host
        # spans; failures are non-fatal (the CPU backend's profiler is
        # optional in some jax builds)
        try:
            import jax.profiler as _jprof
            _jprof.start_trace(opts.trace_device)
            device_trace = _jprof
            print(f"kube-solverd: jax device trace -> {opts.trace_device}",
                  file=sys.stderr)
        except Exception as e:  # pragma: no cover - env-dependent
            print(f"kube-solverd: --trace-device unavailable: {e}",
                  file=sys.stderr)

    srv = SolverService(host=opts.address, port=opts.port,
                        gather_window_s=opts.gather_window,
                        max_batch=opts.max_batch,
                        max_queue=opts.max_queue,
                        cache_entries=opts.cache_entries,
                        mesh=opts.mesh, pods_axis=opts.pods_axis,
                        mesh_min_nodes=opts.mesh_min_nodes,
                        mesh_dispatch=opts.mesh_dispatch,
                        mesh_probe=opts.mesh_probe,
                        prewarm=opts.prewarm,
                        prewarm_nodes=opts.prewarm_nodes,
                        prewarm_pods=opts.prewarm_pods,
                        prewarm_batch=opts.prewarm_batch)
    if opts.flightrec:
        from kubernetes_tpu.util import metrics as metrics_pkg
        metrics_pkg.flightrec_arm("solverd",
                                  period_s=opts.flightrec_period)
    if opts.metrics_port:
        from kubernetes_tpu.cmd.scheduler import _serve_debug
        _serve_debug(opts.metrics_port, service="solverd",
                     health=_solverd_health(srv))
    me = srv._mesh_exec
    mesh_desc = (f", mesh {me.node_shards} node-shards x "
                 f"{me.pods_axis} pods (min {me.min_nodes} nodes, "
                 f"dispatch {opts.mesh_dispatch})"
                 if me is not None else ", mesh off")
    print(f"kube-solverd listening on {srv.address} "
          f"(gather {opts.gather_window * 1000:.1f}ms, "
          f"batch<= {opts.max_batch}, queue<= {opts.max_queue}"
          f"{mesh_desc})",
          file=sys.stderr, flush=True)
    if ready is not None:
        ready.set()
    def _stop_device_trace():
        if device_trace is not None:
            try:
                device_trace.stop_trace()
            except Exception:  # pragma: no cover - profiler teardown
                pass

    if stop is None:
        try:
            srv.serve_forever()
        except KeyboardInterrupt:
            pass
        finally:
            srv.stop()
            _stop_device_trace()
        return 0
    srv.start()
    stop.wait()
    srv.stop()
    _stop_device_trace()
    return 0


def main() -> int:
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(0))
    # the Go-runtime SIGQUIT affordance: kill -USR1 <pid> dumps every
    # thread's stack to stderr (the child log) — the tool of last resort
    # when the daemon wedges hard enough that /debug/pprof can't answer
    import faulthandler
    faulthandler.register(signal.SIGUSR1, all_threads=True)
    return solverd_server(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
