#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system starts on the chip.

One process, one TPU, the normal entry points, the README's north-star
size (5,000 nodes, 10,000 pending pods — bench.FULL_SHAPES["north_star"]):

  phase ``solve``   one wave through models/batch_solver.solve(snap); must
                    run the Pallas kernel; all decisions and scores equal to
                    the XLA scan on the same device, and the first 64 equal
                    to the serial oracle (the commit is sequential, so a
                    prefix is exact).
  phase ``served``  Master + HTTP apiserver on a loopback port +
                    ConfigFactory reflectors + BatchScheduler in this
                    process, no kubelets. Nodes, services and pods arrive
                    over real HTTP, the scheduler lists, watches and binds
                    (``bindings:batch``) over real HTTP; one final LIST is
                    checked in plain Python, independently of the solver.
  ``--four-chips``  runs ONLY the mesh path (40,960 nodes — beyond the
                    kernel's domain — x 1,024 pods through
                    parallel/mesh.solve_sharded, then a full and a delta
                    frame through solver/mesh_exec.MeshExecutor) and its
                    single-device comparison.

The cluster comes from bench.build_cluster, a pure function of its sizes:
no file, no network, no randomness. Every phase prints one JSON line; a
phase that fails raises and the run ends non-zero. The last line is
``{"ok": true, "device": {...}}`` and is printed only after every phase
passed on a TPU. Times printed here are findings for the next issue, not
claims: one run, compiles included where the key says cold.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys
import threading
import time
from unittest import mock

NORTH_STAR = (5_000, 10_000)     # bench.FULL_SHAPES["north_star"]
MESH_WAVE = (40_960, 1_024)      # beyond pallas_solver._MAX_N = 32,640
ORACLE_PODS = 64                 # ~0.3 s per pod at 5,000 nodes
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
PROGRAM_NAMES = {"jit(_solve_pallas_x32)": "kernel", "jit(solve_jit)": "scan",
                 "jit(_unpack_device)": "unpack"}


class SmokeFailure(AssertionError):
    """A phase found something wrong."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def emit(rec: dict) -> None:
    print(json.dumps(_jsonable(rec)), flush=True)


def _jsonable(x):
    if isinstance(x, dict):
        return {str(k): _jsonable(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_jsonable(v) for v in x]
    if isinstance(x, float):
        return None if math.isnan(x) or math.isinf(x) else round(x, 6)
    return x


class CompileLog:
    """Every XLA backend compile of this process by jitted-function name,
    and the persistent cache's hits and misses, as jax.monitoring reports
    them. On a cache hit the 'compile' is the retrieval, near zero."""

    def __init__(self):
        import jax.monitoring as monitoring
        self._lock = threading.Lock()
        self.compiles: list = []     # (fun_name, seconds)
        self.hits = 0
        self.misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, seconds, **kw):
        if event == BACKEND_COMPILE:
            with self._lock:
                self.compiles.append((str(kw.get("fun_name", "?")),
                                      float(seconds)))

    def _on_event(self, event, **kw):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

    def mark(self) -> tuple:
        with self._lock:
            return len(self.compiles), self.hits, self.misses

    def since(self, mark: tuple) -> dict:
        with self._lock:
            new = self.compiles[mark[0]:]
            hits, misses = self.hits - mark[1], self.misses - mark[2]
        by: dict = {}
        for name, s in new:
            key = PROGRAM_NAMES.get(name, "other")
            by[key] = by.get(key, 0.0) + s
        return {"xla_compiles": len(new),
                "compile_s": sum(s for _, s in new),
                "compile_s_by_program": by,
                "cache_hits": hits, "cache_misses": misses}


def _program_counts() -> dict:
    from kubernetes_tpu.models.batch_solver import wave_programs
    return dict(wave_programs().by_label())


def _program_delta(before: dict) -> dict:
    return {f"{prog}@{plat}": int(n - before.get((prog, plat), 0))
            for (prog, plat), n in _program_counts().items()
            if n - before.get((prog, plat), 0)}


def _wave(n_nodes: int, n_pods: int):
    import bench
    from kubernetes_tpu.models.batch_solver import snapshot_to_host_inputs
    from kubernetes_tpu.models.snapshot import encode_snapshot
    objs = bench.build_cluster(n_nodes, n_pods)
    t0 = time.perf_counter()
    snap = encode_snapshot(*objs)
    host = snapshot_to_host_inputs(snap)
    return objs, snap, host, time.perf_counter() - t0


def _readback(chosen, scores):
    import jax.numpy as jnp
    import numpy as np
    both = np.asarray(jnp.stack([chosen, scores]))
    return both[0], both[1]


def _median_s(fn, runs: int = 5) -> float:
    """Median seconds of fn() (which must block until its work is done),
    after one untimed call that pays compiles and per-shape set-up."""
    fn()
    times = []
    for _ in range(runs):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


# --------------------------------------------------------------------------
# phase solve
# --------------------------------------------------------------------------

def phase_solve(clog: CompileLog, n_nodes: int, n_pods: int,
                oracle_pods: int = ORACLE_PODS, warm_runs: int = 3) -> dict:
    import jax
    import numpy as np

    from kubernetes_tpu.models import batch_solver as bs
    from kubernetes_tpu.models.oracle import solve_serial

    backend = jax.default_backend()
    (nodes, existing, pending, services), snap, host, encode_s = \
        _wave(n_nodes, n_pods)
    peer_bound = bs.peer_bound_of(snap)
    plan = bs.default_router.plan_for(host, snap.policy, snap.has_gangs,
                                      peer_bound)

    # the wave, through the normal host entry: encode -> ship ->
    # solve_device -> one readback. Cold pays unpack + kernel compiles.
    before = _program_counts()
    mark = clog.mark()
    t0 = time.perf_counter()
    chosen, scores = bs.solve(snap)
    wave_cold_s = time.perf_counter() - t0
    cold = clog.since(mark)
    wave_warm_s = []
    for _ in range(warm_runs):
        t0 = time.perf_counter()
        again = bs.solve(snap)
        wave_warm_s.append(time.perf_counter() - t0)
        check(np.array_equal(again[0], chosen)
              and np.array_equal(again[1], scores),
              "the same wave solved twice gave different decisions")
    programs = _program_delta(before)
    check(programs == {f"pallas@{backend}": 1 + warm_runs},
          f"waves did not all take the Pallas kernel on {backend}: "
          f"{programs} (route {plan.path})")
    check(chosen.shape == (n_pods,) and scores.shape == (n_pods,)
          and chosen.dtype == np.int32, "decisions have the wrong shape")
    check(int(chosen.min()) >= -1 and int(chosen.max()) < n_nodes,
          "a decision points outside the node list")

    # (i) the XLA scan on the same device: every decision and score
    inp = bs.ship_inputs(host)
    mark = clog.mark()
    t0 = time.perf_counter()
    s_chosen, s_scores = _readback(*bs.solve_jit(
        inp, pol=snap.policy, gangs=snap.has_gangs))
    scan_cold_s = time.perf_counter() - t0
    scan = clog.since(mark)
    t0 = time.perf_counter()
    _readback(*bs.solve_jit(inp, pol=snap.policy, gangs=snap.has_gangs))
    scan_warm_s = time.perf_counter() - t0
    same = int(((chosen == s_chosen) & (scores == s_scores)).sum())
    check(same == n_pods,
          f"kernel and scan agree on only {same}/{n_pods} decisions")

    # (ii) the serial oracle on a prefix of the wave
    k = min(oracle_pods, n_pods)
    t0 = time.perf_counter()
    serial = solve_serial(nodes, existing, pending[:k], services,
                          gangs=True)
    oracle_s = time.perf_counter() - t0
    batch = bs.decisions_to_names(snap, chosen)[:k]
    same_oracle = sum(1 for a, b in zip(batch, serial) if a == b)
    check(same_oracle == k,
          f"kernel and serial oracle agree on only {same_oracle}/{k}")

    # host->device for this one shape: packed single shipment vs plain
    ship_packed_s = _median_s(
        lambda: jax.block_until_ready(bs.pack_and_ship(host)))
    ship_plain_s = _median_s(
        lambda: jax.block_until_ready(
            bs.ship_inputs(host, jax.devices()[0])))

    compile_s = dict(cold["compile_s_by_program"])
    compile_s["scan"] = scan["compile_s_by_program"].get("scan", 0.0)
    return {
        "phase": "solve", "ok": True, "platform": backend,
        "nodes": n_nodes, "pods": n_pods,
        "route": plan.path, "programs": programs,
        "scheduled": int((chosen >= 0).sum()),
        "equal_to_scan": f"{same}/{n_pods}",
        "equal_to_oracle": f"{same_oracle}/{k}",
        "compile_s": compile_s,
        "cache_hits": cold["cache_hits"] + scan["cache_hits"],
        "cache_misses": cold["cache_misses"] + scan["cache_misses"],
        "encode_s": encode_s,
        "wave_cold_s": wave_cold_s, "wave_warm_s": wave_warm_s,
        "scan_wave_cold_s": scan_cold_s, "scan_wave_warm_s": scan_warm_s,
        "ship_default_packed": bs._pack_transfer_enabled(),
        "ship_packed_s": ship_packed_s, "ship_plain_s": ship_plain_s,
        "oracle_s": oracle_s,
    }


# --------------------------------------------------------------------------
# phase served
# --------------------------------------------------------------------------

class _CountingRecorder:
    """The scheduler's event recorder, counted by reason on the way
    through: a wave whose solve raised requeues its pods and binds them a
    second later, so the final LIST alone would not show it."""

    def __init__(self, inner):
        self.inner = inner
        self.by_reason: dict = {}
        self.first_failure = ""
        self._lock = threading.Lock()

    def eventf(self, obj, reason, fmt, *args):
        with self._lock:
            self.by_reason[reason] = self.by_reason.get(reason, 0) + 1
            if reason != "Scheduled" and not self.first_failure:
                self.first_failure = fmt % args if args else fmt
        return self.inner.eventf(obj, reason, fmt, *args)


def _feed(base_url: str, create, items: list, feeders: int) -> None:
    """POST every item over HTTP from ``feeders`` threads, each with its
    own client; the first error ends the run."""
    from kubernetes_tpu.client.client import Client
    from kubernetes_tpu.client.http import HTTPTransport

    errors: list = []

    def run(part):
        client = Client(HTTPTransport(base_url))
        try:
            for obj in part:
                create(client, obj)
        except Exception as e:  # noqa: BLE001 — re-raised by _feed below
            errors.append(e)

    threads = [threading.Thread(target=run, args=(items[f::feeders],),
                                name=f"smoke-feeder-{f}")
               for f in range(feeders)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]


def _wait(predicate, timeout_s: float, what: str) -> None:
    deadline = time.monotonic() + timeout_s
    while not predicate():
        check(time.monotonic() < deadline,
              f"timed out after {timeout_s:.0f}s waiting for {what}")
        time.sleep(0.05)


def _check_final_list(pods, nodes, n_pods: int) -> dict:
    """The served result from one LIST, with no solver code: every pod on
    exactly one existing node, no node over capacity, no host port twice."""
    cap = {n.metadata.name: (n.spec.capacity["cpu"].milli_value(),
                             n.spec.capacity["memory"].int_value())
           for n in nodes}
    names = [p.metadata.name for p in pods]
    check(len(names) == n_pods and len(set(names)) == n_pods,
          f"LIST holds {len(names)} pods ({len(set(names))} distinct), "
          f"expected {n_pods}")
    used: dict = {}
    ports: dict = {}
    for p in pods:
        host = p.spec.host
        check(host and host == p.status.host and host in cap,
              f"pod {p.metadata.name} is bound to {host!r} "
              f"(status {p.status.host!r})")
        cpu, mem = used.get(host, (0, 0))
        for c in p.spec.containers:
            cpu += c.resources.limits["cpu"].milli_value()
            mem += c.resources.limits["memory"].int_value()
            for port in c.ports:
                if port.host_port:
                    taken = ports.setdefault(host, set())
                    check(port.host_port not in taken,
                          f"host port {port.host_port} twice on {host}")
                    taken.add(port.host_port)
        used[host] = (cpu, mem)
    for host, (cpu, mem) in used.items():
        check(cpu <= cap[host][0] and mem <= cap[host][1],
              f"node {host} over capacity: {cpu}m/{mem}B of {cap[host]}")
    return {"nodes_used": len(used),
            "max_cpu_share": max(c / cap[h][0] for h, (c, _) in used.items()),
            "host_port_pods": sum(len(s) for s in ports.values())}


def phase_served(clog: CompileLog, n_nodes: int, n_pods: int,
                 feeders: int = 4, timeout_s: float = 600.0) -> dict:
    import jax

    import bench
    from kubernetes_tpu.api import types as api
    from kubernetes_tpu.apiserver.http import APIServer
    from kubernetes_tpu.apiserver.master import Master, MasterConfig
    from kubernetes_tpu.client.client import Client
    from kubernetes_tpu.client.http import HTTPTransport
    from kubernetes_tpu.client.record import AsyncEventRecorder, EventRecorder
    from kubernetes_tpu.scheduler.driver import ConfigFactory
    from kubernetes_tpu.scheduler.tpu_batch import (BatchScheduler,
                                                    _wave_metrics)
    from kubernetes_tpu.util import metrics

    backend = jax.default_backend()
    nodes, _, pods, services = bench.build_cluster(n_nodes, n_pods,
                                                   existing_per_node=0)
    srv = APIServer(Master(MasterConfig()), host="127.0.0.1", port=0).start()
    factory = sched = events = None
    try:
        t0 = time.perf_counter()
        _feed(srv.base_url, lambda c, o: c.services("default").create(o),
              services, 1)
        _feed(srv.base_url, lambda c, o: c.nodes().create(o), nodes, feeders)
        nodes_s = time.perf_counter() - t0

        # the scheduler as cmd/scheduler builds it: its own HTTP client,
        # rate-limited async events, default wave size and linger
        client = Client(HTTPTransport(srv.base_url,
                                      user_agent="kube-scheduler"))
        events = AsyncEventRecorder(
            EventRecorder(client, api.EventSource(
                component=api.DefaultSchedulerName)), qps=50.0, burst=100)
        recorder = _CountingRecorder(events)
        factory = ConfigFactory(client)
        sched = BatchScheduler(factory.create(recorder=recorder), factory,
                               client)

        t0 = time.perf_counter()
        _feed(srv.base_url, lambda c, o: c.pods("default").create(o), pods,
              feeders)
        pods_s = time.perf_counter() - t0
        _wait(lambda: len(factory.node_store.list()) == n_nodes
              and len(factory.pod_queue.list()) == n_pods, 120.0,
              "the scheduler's reflectors to hold the nodes and the backlog")

        # KTPU_WAVE_ROUTER=device: under 8M cells the router would time a
        # 1,024 x 5,000 wave on the host CPU backend and may send it there
        wm, lat = _wave_metrics(), metrics.pod_latency_metrics()
        waves0, wave_pods0 = wm.solve.count(), wm.pods.total()
        before = _program_counts()
        mark = clog.mark()
        with mock.patch.dict(os.environ, KTPU_WAVE_ROUTER="device"):
            t0 = time.perf_counter()
            sched.run()
            _wait(lambda: len(factory.scheduled_pods.list()) >= n_pods,
                  timeout_s, f"{n_pods} pods to be bound")
            wall_s = time.perf_counter() - t0
            sched.stop()
            # the fill-trigger prewarm thread finishes the bucket it is
            # compiling (through the same dispatch) before it sees the stop
            for t in threading.enumerate():
                if t.name == "sched-prewarm-compile":
                    t.join(timeout=300.0)
                    check(not t.is_alive(), "the prewarm thread did not stop")
        compiles = clog.since(mark)
        waves = wm.solve.count() - waves0
        programs = _program_delta(before)

        user = Client(HTTPTransport(srv.base_url))
        listed = _check_final_list(user.pods(api.NamespaceAll).list().items,
                                   user.nodes().list().items, n_pods)
        check(not set(recorder.by_reason) - {"Scheduled"},
              f"the scheduler recorded {recorder.by_reason}: "
              f"{recorder.first_failure}")
        check(wm.pods.total() - wave_pods0 == n_pods,
              f"{wm.pods.total() - wave_pods0:.0f} pods went through "
              f"solved waves, expected each of {n_pods} once")
        # prewarm dispatches count too, so >=: what must not appear is
        # any other program or platform
        check(set(programs) == {f"pallas@{backend}"}
              and programs[f"pallas@{backend}"] >= waves,
              f"not every wave took the Pallas kernel on {backend}: "
              f"{programs} over {waves} waves")
        batch_binds = {code: int(n) for (_verb, res, _client, code), n
                       in srv.metric_requests.by_label().items()
                       if res == "bindings:batch"}
        check(batch_binds and set(batch_binds) == {"200"},
              f"bindings:batch requests by code: {batch_binds}")
        return {
            "phase": "served", "ok": True, "platform": backend,
            "nodes": n_nodes, "pods_bound": f"{n_pods}/{n_pods}",
            "waves": waves, "programs": programs,
            "wave_router": "KTPU_WAVE_ROUTER=device (pinned by the smoke)",
            "backlog_first": True,
            "wall_s": wall_s, "pods_per_s_incl_compiles": n_pods / wall_s,
            "register_nodes_s": nodes_s, "create_pods_s": pods_s,
            "create_to_bound_s_p50": lat.e2e.quantile(0.5),
            "create_to_bound_s_p99": lat.e2e.quantile(0.99),
            "wave_solve_s_p50": wm.solve.quantile(0.5),
            "wave_encode_s_p50": wm.encode.quantile(0.5),
            "wave_commit_s_p50": wm.commit.quantile(0.5),
            "bindings_batch_requests": batch_binds,
            "events": recorder.by_reason,
            "prewarm_buckets_compiled":
                int(metrics.slipstream_metrics().prewarm_total.total()),
            **listed, **compiles,
        }
    finally:
        if sched is not None:
            sched.stop()
        if factory is not None:
            factory.stop()
        if events is not None:
            events.stop()
        srv.stop()


def router_calibration(n_nodes: int, wave_pods: int) -> dict:
    """One WaveRouter calibration of the served path's wave bucket: the
    whole pipeline (ship + solve + readback) on the device against the
    host CPU backend. For the next issue: does the router still earn its
    place on a directly attached chip?"""
    from kubernetes_tpu.models import batch_solver as bs

    _objs, snap, host, _ = _wave(n_nodes, wave_pods)
    with mock.patch.dict(os.environ, KTPU_WAVE_ROUTER="auto"):
        t0 = time.perf_counter()
        plan = bs.WaveRouter().plan_for(host, snap.policy, snap.has_gangs,
                                        bs.peer_bound_of(snap))
        cal_s = time.perf_counter() - t0
    return {"phase": "router_calibration", "ok": True,
            "nodes": n_nodes, "pods": wave_pods,
            "calibrated": not math.isnan(plan.host_s),
            "winner": plan.path, "host_s": plan.host_s,
            "device_s": plan.device_s, "calibration_s": cal_s}


# --------------------------------------------------------------------------
# --four-chips: the mesh path and what it is compared with
# --------------------------------------------------------------------------

def _on_mesh(where: str, arrays: dict, mesh_ids: list) -> dict:
    """{plane: per-device shard shape}, after checking that every plane
    has a shard on every device of the mesh — "everything on the first
    chip" must not pass unseen."""
    shapes = {}
    for name, a in arrays.items():
        ids = sorted(s.device.id for s in a.addressable_shards)
        check(ids == mesh_ids, f"{where} plane {name} lives on devices "
                               f"{ids}, not on the mesh {mesh_ids}")
        shapes[name] = list(a.addressable_shards[0].data.shape)
    return {"devices": mesh_ids, "shard_shapes": shapes}


def phase_mesh(clog: CompileLog, n_nodes: int, n_pods: int) -> dict:
    import jax
    import numpy as np

    from kubernetes_tpu.models import batch_solver as bs
    from kubernetes_tpu.models.policy import BatchPolicy
    from kubernetes_tpu.ops import pallas_solver
    from kubernetes_tpu.parallel import mesh as pm
    from kubernetes_tpu.solver.mesh_exec import MeshExecutor

    mark = clog.mark()
    _objs, snap, host, _ = _wave(n_nodes, n_pods)
    pol = snap.policy or BatchPolicy()
    peer_bound = bs.peer_bound_of(snap)
    mesh = pm.make_mesh(pods_axis=1)
    mesh_ids = sorted(d.id for d in mesh.devices.flat)
    dev0 = jax.devices()[0]

    def single_device(h):
        return _readback(*bs.solve_jit(bs.ship_inputs(h, dev0), pol=pol,
                                       gangs=False))

    def same(a, b, what):
        n = int(((a[0] == b[0]) & (a[1] == b[1])).sum())
        check(n == n_pods, f"{what}: {n}/{n_pods} equal to the single-"
                           f"device scan")
        return f"{n}/{n_pods}"

    # one wave through solve_sharded; prefer_kernel=False because this
    # phase is the mesh's (at 40,960 nodes the kernel is out of domain
    # anyway: kernel_eligible below)
    t0 = time.perf_counter()
    sharded = pm.solve_sharded(host, mesh, pol=pol, gangs=False,
                               peer_bound=peer_bound, prefer_kernel=False)
    sharded_cold_s = time.perf_counter() - t0
    sharded_warm_s = _median_s(lambda: pm.solve_sharded(
        host, mesh, pol=pol, gangs=False, peer_bound=peer_bound,
        prefer_kernel=False), runs=3)
    t0 = time.perf_counter()
    ref = single_device(host)
    single_cold_s = time.perf_counter() - t0
    single_warm_s = _median_s(lambda: single_device(host), runs=3)
    eq_sharded = same(sharded, ref, "solve_sharded")
    # where solve_sharded's own placement puts each plane
    padded, _n = pm.pad_inputs_for_mesh(host, mesh)
    shardings = pm.input_shardings(mesh)
    placed = _on_mesh("solve_sharded", {
        f: jax.device_put(getattr(padded, f), getattr(shardings, f))
        for f in bs.SolverInputs._fields}, mesh_ids)

    # two waves through the daemon's executor: a full frame, then a delta
    # (wave 1's placements committed onto the usage planes, row-wise)
    ex = MeshExecutor(dispatch="shard")
    key = ("chip-smoke", "bucket0")
    full = ex.solve(host, pol, False, cache_key=key)
    eq_full = same(full, ref, "MeshExecutor full frame")
    hit = full[0] >= 0
    rows = np.unique(full[0][hit]).astype(np.int64)
    fit2, score2 = host.fit_used.copy(), host.score_used.copy()
    np.add.at(fit2, full[0][hit], host.req[hit])
    np.add.at(score2, full[0][hit], host.req[hit])
    host2 = host._replace(fit_used=fit2, score_used=score2)
    transfer0 = ex._m.transfer_bytes.value()
    reshard0 = ex._m.reshard_bytes.value()
    delta = ex.solve(host2, pol, False, cache_key=key, delta={
        "fit_used": (host.fit_used, rows, fit2[rows]),
        "score_used": (host.score_used, rows, score2[rows])})
    eq_delta = same(delta, single_device(host2), "MeshExecutor delta frame")
    check(not np.array_equal(delta[0], full[0]),
          "the delta frame changed no decision: it did not reach the device")
    resident = _on_mesh("MeshExecutor", {
        name: rec[1] for name, rec in ex._resident[key]["planes"].items()},
        mesh_ids)
    check(ex.parity_divergent == 0, "MeshExecutor's own parity probe diverged")
    return {
        "phase": "mesh", "ok": True, "platform": jax.default_backend(),
        "nodes": n_nodes, "pods": n_pods,
        "mesh": dict(mesh.shape), "mesh_devices": mesh_ids,
        "kernel_eligible": pallas_solver.eligible(host, pol, False,
                                                  peer_bound),
        "solve_sharded_equal": eq_sharded,
        "mesh_exec_full_equal": eq_full, "mesh_exec_delta_equal": eq_delta,
        "mesh_exec": {"node_shards": ex.node_shards,
                      "mesh_waves": ex.mesh_waves,
                      "parity_checks": ex.parity_checks,
                      "delta_transfer_bytes":
                          int(ex._m.transfer_bytes.value() - transfer0),
                      "delta_reshard_bytes":
                          int(ex._m.reshard_bytes.value() - reshard0)},
        "sharded_wave_cold_s": sharded_cold_s,
        "sharded_wave_warm_s": sharded_warm_s,
        "single_wave_cold_s": single_cold_s,
        "single_wave_warm_s": single_warm_s,
        "solve_sharded_planes": placed,
        "mesh_exec_resident_planes": resident,
        **clog.since(mark),
    }


# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh path (solve_sharded and "
                         "MeshExecutor on a 1x4 mesh) and its single-"
                         "device comparison; needs four chips")
    args = ap.parse_args(argv)

    import jax

    # the host CPU backend beside the chip, as bench.py exposes it: the
    # wave router's host route (router_calibration) needs a CPU device
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        jax.config.update("jax_platforms", plats + ",cpu")
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        print(f"chip_smoke: no TPU (JAX reports {device}); nothing was run",
              file=sys.stderr)
        return 1
    if args.four_chips and len(devices) != 4:
        print(f"chip_smoke: --four-chips needs 4 chips, found {len(devices)}",
              file=sys.stderr)
        return 1

    from kubernetes_tpu.util import warmstart
    warmstart.enable()
    emit({"phase": "start", "device": device, "jax": jax.__version__,
          "compile_cache_dir": jax.config.jax_compilation_cache_dir,
          "JAX_COMPILATION_CACHE_DIR":
              os.environ.get("JAX_COMPILATION_CACHE_DIR")})
    clog = CompileLog()
    if args.four_chips:
        emit(phase_mesh(clog, *MESH_WAVE))
    else:
        emit(phase_solve(clog, *NORTH_STAR))
        emit(phase_served(clog, *NORTH_STAR))
        emit(router_calibration(NORTH_STAR[0], 1_024))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
