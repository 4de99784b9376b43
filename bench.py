"""Benchmark: batch-scheduler throughput over the BASELINE config matrix.

Emits ONE COMPACT JSON line (guaranteed < 1.5 KB, parseable with
json.loads — per-config value/p50/p99/path/gate only) and writes the full
record — runs_s arrays, router calibration detail, component breakdowns —
to a sibling detail file (``--detail-out``, default BENCH_detail.json
next to this script). The primary metric is the north-star config
(BASELINE.md: bind 10k pending pods onto 5k nodes in one TPU solve,
decisions bit-identical to the serial reference path; the reference target
docs/roadmap.md:61 — 99% of decisions < 1s at 100 nodes / 3000 pods —
normalizes to 10_000 pods/s, so vs_baseline = pods_per_sec / 10_000). The
``configs`` object carries one record per BASELINE.json config, each with
its own equivalence gate:

  north_star      10k pods x 5k nodes — FULL-scale serial-oracle equivalence
  basic           1k pods x 500 nodes (scheduler_perf SchedulingBasic)
  affinity        5k x 5k with zone anti-affinity policy (SchedulingPodAffinity's
                  v0-era ancestor: ServiceAntiAffinity zone spreading)
  binpack3        10k x 5k with THREE resource dimensions + service spread
  gang            1k PodGroups x 8 pods all-or-nothing on 2k nodes
  churn           pods offered at 1k/s through the REAL BatchScheduler +
                  apiserver + reflectors (incremental encoder path)

Honest timing: a wave costs encode + host->device transfer + solve +
decision readback; every timed run performs all four inside the clock and
the reported wave is the median run (wave_s_min/wave_s_max bound the
spread). Two once-per-shape costs are excluded but logged: XLA compilation
(compile_s) and the transfer path's per-shape setup (shape_setup_s) —
pow-2 bucketing bounds the shape count, and the churn config proves the
steady-shape regime end-to-end through the live scheduler stack.

One process: it imports JAX, holds the chip and runs the matrix. A run
that finds no TPU fails with an error record unless it was given
``--smoke`` (small shapes on the CPU backend, for CI); every record names
the device it ran on (``platform``, ``device_kind``, ``device_count``).
The cumulative record is printed after every config, so the last JSON
line on stdout is always the newest truth.

Usage: python bench.py [--smoke] [--pods P] [--nodes N] [--configs a,b,..]
                       [--profile DIR] [--detail-out FILE]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# reference target: 99% of decisions < 1s at 100 nodes / 3000 pods
# (docs/roadmap.md:61) normalizes to 10k pods/s — see module docstring
BASELINE_PODS_PER_S = 10_000.0
TIMING_DESC = ("steady-state wave: encode + host->device + solve + readback, "
               "no sync between transfer and solve (median run; see "
               "timed_wave)")


# --------------------------------------------------------------------------
# Compact emission: the final stdout line must stay machine-parseable.
# --------------------------------------------------------------------------

_COMPACT_BUDGET = 1400  # bytes; hard contract is < 1.5 KB

# per-config keys kept on the compact line, in drop order under pressure
# (the full record always lands in the detail file)
_COMPACT_CFG_KEYS = (
    ("value", ("value",)),
    ("p50", ("wave_s_p50", "p50")),
    ("p99", ("wave_s_p99", "p99")),
    ("path", ("path",)),
    ("gate", ("gate",)),
)


def _compact_record(rec: dict, detail_name=None) -> str:
    """The <1.5 KB stdout summary of a full benchmark record: top-level
    verdict + per-config value/p50/p99/path/gate. BENCH_r05.json had parsed:null
    because one giant line (runs_s arrays inline) truncated in capture —
    arrays and calibration detail now live in the detail file only.
    Degrades by dropping optional keys before it would ever exceed the
    budget."""
    out = {}
    for k in ("metric", "value", "unit", "vs_baseline", "platform",
              "device_kind", "device_count", "partial"):
        if k in rec:
            out[k] = rec[k]
    if "error" in rec:
        out["error"] = str(rec["error"])[:300]
    if detail_name:
        out["detail"] = detail_name
    elif "detail" in rec:
        out["detail"] = rec["detail"]
    cfgs = {}
    for tag, c in (rec.get("configs") or {}).items():
        cc = {}
        for short, sources in _COMPACT_CFG_KEYS:
            for s in sources:
                if isinstance(c, dict) and s in c:
                    cc[short] = c[s]
                    break
        cfgs[tag] = cc
    if cfgs:
        out["configs"] = cfgs
    line = json.dumps(out, separators=(",", ":"))
    drops = [k for k, _ in reversed(_COMPACT_CFG_KEYS) if k != "value"]
    while len(line) > _COMPACT_BUDGET and drops:
        drop = drops.pop(0)
        for cc in cfgs.values():
            cc.pop(drop, None)
        line = json.dumps(out, separators=(",", ":"))
    if len(line) > _COMPACT_BUDGET:
        out.pop("configs", None)
        out["configs_in_detail_only"] = sorted(cfgs)
        line = json.dumps(out, separators=(",", ":"))
    return line


def _write_detail(path: str, rec: dict) -> None:
    """Best-effort full-record sidecar; the capture must survive a
    read-only filesystem."""
    if not path:
        return
    try:
        tmp = path + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(rec, fh, indent=1)
            fh.write("\n")
        os.replace(tmp, path)
    except OSError as e:
        log(f"[bench] detail file {path!r} unwritable: {e}")


def _error_record(reason: str, device: dict = None) -> str:
    """A zero-value record carrying the reason the run measured nothing
    (and the device JAX reported, where it got that far)."""
    return json.dumps({
        "metric": "pods_scheduled_per_sec", "value": 0.0,
        "unit": "pods/s", "vs_baseline": 0.0, "error": reason[-800:],
        **(device or {})})


# --------------------------------------------------------------------------
# The benchmarks.
# --------------------------------------------------------------------------

def affinity_policy():
    """The anti-affinity benchmark policy: the full default predicate set
    + zone spreading. Single definition shared by the bench matrix and
    hack/fullgate.py so the out-of-band full-scale gate always certifies
    exactly the config the benchmark runs."""
    from kubernetes_tpu.scheduler.plugins import (Policy, PolicyPredicate,
                                                  PolicyPriority)
    return Policy(
        predicates=[PolicyPredicate(name=n) for n in
                    ("PodFitsPorts", "PodFitsResources", "NoDiskConflict",
                     "MatchNodeSelector", "HostName")],
        priorities=[PolicyPriority(name="LeastRequestedPriority", weight=1),
                    PolicyPriority(name="zoneSpread", weight=2,
                                   service_anti_affinity_label="zone")])


# full-scale shapes per solver config: (nodes, pods, build_cluster kwargs);
# the policy for "affinity" is affinity_policy(). Shared with fullgate.
FULL_SHAPES = {
    "north_star": (5_000, 10_000, {}),
    "basic": (500, 1_000, {}),
    "affinity": (5_000, 5_000, {}),
    "binpack3": (5_000, 10_000, {"three_resources": True}),
    "gang": (2_000, 0, {"gang_groups": 1_000, "gang_size": 8}),
    "mesh": (10_000, 2_048, {}),
    "priority": (2_000, 1_000, {}),
}


def build_cluster(n_nodes: int, n_pods: int, n_services: int = 8,
                  existing_per_node: int = 2, three_resources: bool = False,
                  gang_groups: int = 0, gang_size: int = 8):
    from kubernetes_tpu.api import types as api
    from kubernetes_tpu.api.quantity import Quantity
    from kubernetes_tpu.models import gang as gang_mod

    caps = {"cpu": Quantity("16"), "memory": Quantity("64Gi")}
    if three_resources:
        caps["ephemeral-storage"] = Quantity("256Gi")
    nodes = [api.Node(
        metadata=api.ObjectMeta(name=f"node-{i:05d}",
                                labels={"zone": f"z{i % 16}",
                                        "disk": "ssd" if i % 4 else "hdd"}),
        spec=api.NodeSpec(capacity=dict(caps)))
        for i in range(n_nodes)]
    services = [api.Service(
        metadata=api.ObjectMeta(name=f"svc-{s}", namespace="default"),
        spec=api.ServiceSpec(port=80, selector={"app": f"app-{s}"}))
        for s in range(n_services)]

    def pod(name, i, host="", group=None):
        limits = {"cpu": Quantity(f"{100 + (i % 8) * 100}m"),
                  "memory": Quantity(f"{128 + (i % 6) * 256}Mi")}
        if three_resources:
            limits["ephemeral-storage"] = Quantity(f"{1 + (i % 4)}Gi")
        ann = {}
        if group is not None:
            ann[gang_mod.GANG_NAME_ANNOTATION] = group
            ann[gang_mod.GANG_MIN_MEMBERS_ANNOTATION] = str(gang_size)
        return api.Pod(
            metadata=api.ObjectMeta(
                name=name, namespace="default", uid=f"uid-{name}",
                labels={"app": f"app-{i % n_services}"}, annotations=ann),
            spec=api.PodSpec(
                host=host,
                containers=[api.Container(
                    name="c", image="img",
                    ports=[api.ContainerPort(container_port=80,
                                             host_port=7000 + (i % 50))]
                    if i % 10 == 0 else [],
                    resources=api.ResourceRequirements(limits=limits))]),
            status=api.PodStatus(host=host))

    existing = [pod(f"old-{n}-{j}", n * existing_per_node + j,
                    host=nodes[n].metadata.name)
                for n in range(n_nodes) for j in range(existing_per_node)]
    if gang_groups:
        pending = [pod(f"g{g:04d}-m{m}", g * gang_size + m,
                       group=f"group-{g:04d}")
                   for g in range(gang_groups) for m in range(gang_size)]
    else:
        pending = [pod(f"new-{i:05d}", i) for i in range(n_pods)]
    return nodes, existing, pending, services


def timed_wave(nodes, existing, pending, services, batch_policy=None,
               profile=None, runs: int = 30):
    """One honest scheduling wave, measured at steady state: every timed
    run performs the FULL pipeline — snapshot encode (numpy), host->device
    transfer, solve, decision readback (+ gang post-pass) — inside the
    clock; the reported wave is the median run and the record carries the
    full per-run distribution (p50/p95/p99/max over >=30 runs — BASELINE's
    metric is pods/s + p99 latency, ref: docs/roadmap.md:61). One untimed
    warmup pass first pays the per-shape costs a live scheduler pays once
    and then never again: XLA compilation and the transfer path's
    per-shape setup (the packed transfer's unpack program is a compile
    of its own per shape; pow-2 bucketing keeps the shape set finite,
    which the churn config proves end-to-end). Both one-time costs are
    logged. Small waves route through the measured
    host-vs-device dispatch (batch_solver.WaveRouter); the chosen path
    and both calibration times land in the record. Returns a result dict
    and the decisions from the last run."""
    import jax
    import numpy as np

    from kubernetes_tpu.models import gang as gang_mod
    from kubernetes_tpu.models.batch_solver import (
        default_router,
        peer_bound_of,
        ship_inputs,
        snapshot_to_host_inputs,
        solve_device,
    )
    from kubernetes_tpu.models.snapshot import encode_snapshot

    # -- untimed warmup: router calibration + compile + shape setup ---------
    snap = encode_snapshot(nodes, existing, pending, services,
                           policy=batch_policy)
    gangs = snap.has_gangs
    peer_bound = peer_bound_of(snap)
    host = snapshot_to_host_inputs(snap)
    t0 = time.perf_counter()
    plan = default_router.plan_for(host, snap.policy, gangs, peer_bound)
    router_s = time.perf_counter() - t0
    force_scan = plan.device is not None
    calibrated = plan.host_s == plan.host_s  # not nan
    if plan.path == "host":
        log(f"[router] host CPU wins this shape: host {plan.host_s:.4f}s "
            f"vs device {plan.device_s:.4f}s (calibrated in {router_s:.1f}s)")
    if calibrated:
        # calibration already paid the one-time costs (both backends
        # compiled inside plan_for), so compile_s/shape_setup_s are not
        # separately measurable — the record carries the chosen path's
        # cold first pipeline as cold_pipeline_s instead, plus the full
        # calibration bill as router_cal_s; compile_s/shape_setup_s are
        # OMITTED rather than reported as warm-cache numbers
        shape_setup_s = None
        compile_s = None
    else:
        t0 = time.perf_counter()
        inp = ship_inputs(host, plan.device)
        jax.block_until_ready(inp)
        shape_setup_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = solve_device(inp, snap.policy, gangs, peer_bound,
                           force_scan=force_scan)
        jax.block_until_ready(out)
        compile_s = time.perf_counter() - t0

    def one_wave():
        """The FULL wave pipeline, exactly as a live scheduler runs it:
        encode, then ship with no sync between transfer and solve (the
        dispatch pipelines the uploads into the device call; the decision
        readback is the one sync), then the gang post-pass.
        Returns (snap, decisions, encode_end_t)."""
        snap = encode_snapshot(nodes, existing, pending, services,
                               policy=batch_policy)
        host = snapshot_to_host_inputs(snap)
        t_enc = time.perf_counter()
        inp = ship_inputs(host, plan.device)
        chosen, _scores = solve_device(inp, snap.policy, gangs, peer_bound,
                                       force_scan=force_scan)
        chosen_np = np.asarray(chosen)      # device->host readback (sync)
        if gangs:
            chosen_np = gang_mod.apply_all_or_nothing(snap.pod_rid, chosen_np)
        return snap, chosen_np, t_enc

    # -- one untimed COLD full pass ------------------------------------------
    # The first unsynced pass may pay one-time costs the sequential
    # warmup above did not. A live scheduler pays them once per process;
    # pay and log them here so the timed distribution is pure steady
    # state.
    t0 = time.perf_counter()
    one_wave()
    cold_pipeline_s = time.perf_counter() - t0

    # -- timed steady-state runs: the whole pipeline in the clock -----------
    if profile:
        jax.profiler.start_trace(profile)
    wave_runs, parts = [], []
    chosen_np = None
    for _ in range(runs):
        t0 = time.perf_counter()
        snap, chosen_np, t1 = one_wave()
        t2 = time.perf_counter()
        wave_runs.append(t2 - t0)
        parts.append((t1 - t0, t2 - t1))
    if profile:
        jax.profiler.stop_trace()
        log(f"jax.profiler trace written to {profile}")

    srt = sorted(wave_runs)
    p50, p95, p99 = (float(v) for v in
                     np.percentile(wave_runs, [50.0, 95.0, 99.0]))
    # the median RUN (upper middle for even counts): wave_s and its
    # component breakdown come from the same run, so the parts sum to it
    wave_med = srt[len(srt) // 2]
    encode_s, device_s = parts[wave_runs.index(wave_med)]
    for i, w in enumerate(wave_runs):       # tail forensics in the log
        if w > 2 * wave_med:
            log(f"[tail] run {i}/{runs}: {w:.3f}s (median {wave_med:.3f}s)")
    n = len(pending)
    res = {
        "pods": n,
        "nodes": len(nodes),
        "value": round(n / wave_med, 1),
        "unit": "pods/s",
        "wave_s": round(wave_med, 4),
        "wave_s_min": round(srt[0], 4),
        "wave_s_max": round(srt[-1], 4),
        "wave_s_p50": round(p50, 4),
        "wave_s_p95": round(p95, 4),
        "wave_s_p99": round(p99, 4),
        "runs": runs,
        "runs_s": [round(w, 4) for w in wave_runs],
        "path": plan.path,
        "encode_s": round(encode_s, 4),
        "device_s": round(device_s, 4),
        "scheduled": int((chosen_np[:n] >= 0).sum()),
    }
    res["cold_pipeline_s"] = round(cold_pipeline_s, 3)
    if calibrated:
        res["router_host_s"] = round(plan.host_s, 4)
        res["router_device_s"] = round(plan.device_s, 4)
        res["router_cal_s"] = round(router_s, 2)
        res["router_cold_s"] = round(plan.cold_s, 3)
    else:
        res["compile_s"] = round(compile_s, 3)
        res["shape_setup_s"] = round(shape_setup_s, 3)
    return res, snap, chosen_np


def build_priority_cluster(n_nodes: int, n_pending: int,
                           fill_per_node: int = 4):
    """kube-preempt benchmark cluster: every node pre-filled EXACTLY to
    capacity with low-priority pods split across two priority bands (so
    the lowest-sufficient-threshold choice is non-trivial), then a
    pending wave that can only place by evicting — plus Never-policy and
    equal-priority pods that must stay pending (the invariants ride the
    same wave the throughput number comes from)."""
    from kubernetes_tpu.api import types as api
    from kubernetes_tpu.api.quantity import Quantity

    unit_m = 500
    nodes = [api.Node(
        metadata=api.ObjectMeta(name=f"node-{i:05d}"),
        spec=api.NodeSpec(capacity={
            "cpu": Quantity(f"{fill_per_node * unit_m}m"),
            "memory": Quantity("32Gi")}))
        for i in range(n_nodes)]

    def pod(name, i, prio, host="", policy_never=False, units=1):
        return api.Pod(
            metadata=api.ObjectMeta(name=name, namespace="default",
                                    uid=f"uid-{name}"),
            spec=api.PodSpec(
                host=host,
                containers=[api.Container(
                    name="c", image="img",
                    resources=api.ResourceRequirements(limits={
                        "cpu": Quantity(f"{units * unit_m}m"),
                        "memory": Quantity(f"{units * 256}Mi")}))],
                priority=prio,
                preemption_policy=(api.PreemptNever if policy_never
                                   else "")),
            status=api.PodStatus(host=host))

    existing = []
    for i in range(n_nodes):
        for j in range(fill_per_node):
            # two low bands: 100 and 200 — a preemptor may clear just the
            # 100 band (lowest sufficient) or need both
            existing.append(pod(f"low-{i:05d}-{j}", i,
                                100 if j % 2 == 0 else 200,
                                host=f"node-{i:05d}"))
    pending = []
    for k in range(n_pending):
        if k % 10 == 9:
            # PreemptionPolicy=Never at high priority: stays pending in a
            # full cluster no matter what
            pending.append(pod(f"storm-never-{k:05d}", k, 1000,
                               policy_never=True))
        elif k % 10 == 8:
            # equal priority to the top resident band: never evicts
            pending.append(pod(f"storm-equal-{k:05d}", k, 200))
        else:
            # the storm: single- and double-unit high-priority pods
            pending.append(pod(f"storm-{k:05d}", k, 1000,
                               units=1 + (k % 3 == 0)))
    return nodes, existing, pending


def run_priority_config(tag, n_nodes, n_pods, gate_nodes=0, gate_pods=0,
                        runs=30):
    """kube-preempt: throughput of preemption waves (every placement
    evicts) + the bit-identity gate against the preempt_serial oracle —
    decisions AND victim sets must match exactly, and the
    never-evict-equal-or-higher / PreemptionPolicy=Never invariants are
    re-checked on the full wave."""
    import numpy as np

    from kubernetes_tpu.api import types as api
    from kubernetes_tpu.models import preempt as preempt_mod
    from kubernetes_tpu.models.batch_solver import decisions_to_names, solve
    from kubernetes_tpu.models.oracle import preempt_serial
    from kubernetes_tpu.models.snapshot import encode_snapshot

    log(f"[{tag}] building full cluster: {n_nodes} nodes pre-filled, "
        f"{n_pods} storm pods")
    nodes, existing, pending = build_priority_cluster(n_nodes, n_pods)
    res, snap, chosen_np = timed_wave(nodes, existing, pending, [],
                                      runs=runs)

    # full-wave invariant checks need the scores (timed_wave drops them):
    # one more solve of the same snapshot — deterministic, cached program
    chosen, scores = solve(snap)
    assert np.array_equal(np.asarray(chosen), np.asarray(chosen_np)), \
        "non-deterministic priority solve"
    names = decisions_to_names(snap, chosen)
    node_index = {n.metadata.name: i for i, n in enumerate(nodes)}
    victims = preempt_mod.assign_victims(
        chosen, scores, snap.band_prio,
        preempt_mod.resident_from_pods(existing, node_index),
        n_pods=len(pending))
    prio_of = {p.metadata.uid: api.pod_priority(p) for p in existing}
    n_preempted = sum(1 for v in victims if v)
    n_victims = sum(len(v) for v in victims if v)
    for p, v in zip(pending, victims):
        if not v:
            continue
        pp = api.pod_priority(p)
        assert all(prio_of[x.uid] < pp for x in v), \
            f"{tag}: evicted an equal-or-higher-priority pod"
        assert p.spec.preemption_policy != api.PreemptNever, \
            f"{tag}: a PreemptionPolicy=Never pod preempted"
    # Never pods may still place NORMALLY into capacity earlier
    # preemptions freed (a whole evicted band can exceed its preemptor's
    # request) — what they may never do is place via eviction, which the
    # victims loop above already pinned. Re-assert it explicitly:
    never_evicting = [nm for p, nm, v in zip(pending, names, victims)
                      if p.spec.preemption_policy == api.PreemptNever and v]
    assert not never_evicting, \
        f"{tag}: Never pods placed via preemption: {never_evicting}"
    res["preempted_pods"] = n_preempted
    res["victims"] = n_victims
    log(f"[{tag}] {n_preempted} preempting placements, {n_victims} "
        f"victims, invariants OK")

    # oracle gate: decisions + victim sets bit-identical to preempt_serial
    g_nodes = nodes[:gate_nodes] if gate_nodes else nodes
    keep = {n.metadata.name for n in g_nodes}
    g_exist = [p for p in existing if p.status.host in keep]
    g_pend = pending[:gate_pods] if gate_pods else pending
    g_snap = encode_snapshot(g_nodes, g_exist, g_pend, [])
    g_chosen, g_scores = solve(g_snap)
    g_names = decisions_to_names(g_snap, g_chosen)
    g_index = {n.metadata.name: i for i, n in enumerate(g_nodes)}
    g_victims = preempt_mod.assign_victims(
        g_chosen, g_scores, g_snap.band_prio,
        preempt_mod.resident_from_pods(g_exist, g_index),
        n_pods=len(g_pend))
    t0 = time.perf_counter()
    s_names, s_victims = preempt_serial(g_nodes, g_exist, g_pend)
    oracle_s = time.perf_counter() - t0
    bv = [sorted(v.uid for v in (x or [])) or None for x in g_victims]
    sv = [sorted(v.uid for v in (x or [])) or None for x in s_victims]
    if g_names != s_names or bv != sv:
        nd = sum(1 for a, b in zip(g_names, s_names) if a != b)
        nv = sum(1 for a, b in zip(bv, sv) if a != b)
        log(f"[{tag}] PREEMPT ORACLE FAILURE: {nd} decisions / {nv} "
            f"victim sets diverge over {len(g_pend)} pods")
        return None
    rate = len(g_pend) / oracle_s if oracle_s > 0 else 0.0
    res["gate"] = f"preempt-oracle-{len(g_pend)}x{len(g_nodes)}"
    res["serial_oracle_pods_per_s"] = round(rate, 1)
    log(f"[{tag}] preempt oracle OK: decisions + victim sets identical "
        f"on {len(g_pend)} pods x {len(g_nodes)} nodes "
        f"({oracle_s:.1f}s serial)")
    return res


def check_equivalence(tag, snap, chosen_np, nodes, existing, pending,
                      services, policy=None):
    """Batch decisions vs the serial oracle over the same wave."""
    from kubernetes_tpu.models.batch_solver import decisions_to_names
    from kubernetes_tpu.models.oracle import solve_serial

    t0 = time.perf_counter()
    serial = solve_serial(nodes, existing, pending, services, policy=policy,
                          gangs=True)
    serial_s = time.perf_counter() - t0
    batch = decisions_to_names(snap, chosen_np)
    if batch != serial:
        n_div = sum(1 for a, b in zip(batch, serial) if a != b)
        log(f"[{tag}] EQUIVALENCE FAILURE: {n_div}/{len(serial)} diverge")
        return None
    rate = len(pending) / serial_s if serial_s > 0 else 0.0
    log(f"[{tag}] equivalence OK on {len(pending)} pods x {len(nodes)} "
        f"nodes; serial oracle {rate:.0f} pods/s")
    return rate


def run_solver_config(tag, n_nodes, n_pods, gate_nodes=0, gate_pods=0,
                     policy=None, three_resources=False, gang_groups=0,
                     gang_size=8, profile=None, full_gate=False,
                     gate_budget_s=75.0, runs=30):
    """Benchmark one solver-path config. Gate variants: full_gate runs the
    serial oracle over the whole wave; gate_pods/gate_nodes take a fixed
    slice; gate_pods=0 with gate_nodes=0 sizes the pod slice to
    ``gate_budget_s`` of measured serial-oracle time over the FULL node
    axis (the serial cost scales with node count, so a full 10k x 5k
    oracle is ~20min — budget-sized slices keep the node-axis effects,
    where divergence would hide, while keeping a run short; the
    complete full-scale run is recorded out-of-band in FULLGATE_r03.json).
    Returns the result dict or None on gate failure."""
    log(f"[{tag}] building {n_pods} pods x {n_nodes} nodes"
        + (" (3 resources)" if three_resources else "")
        + (f" ({gang_groups} gangs x {gang_size})" if gang_groups else ""))
    nodes, existing, pending, services = build_cluster(
        n_nodes, n_pods, three_resources=three_resources,
        gang_groups=gang_groups, gang_size=gang_size)

    from kubernetes_tpu.models.policy import batch_policy_from
    batch_policy = batch_policy_from(policy=policy) if policy else None
    res, snap, chosen_np = timed_wave(nodes, existing, pending, services,
                                      batch_policy=batch_policy,
                                      profile=profile, runs=runs)

    if full_gate:
        g_nodes, g_exist, g_pend = nodes, existing, pending
        g_snap, g_chosen = snap, chosen_np
        res["gate"] = f"full-oracle-{len(pending)}x{len(nodes)}"
    else:
        g_nodes = nodes[:gate_nodes] if gate_nodes else nodes
        keep = {n.metadata.name for n in g_nodes}
        g_exist = [p for p in existing if p.status.host in keep]
        if gang_groups:
            per = max(1, gate_pods // gang_size)
            g_pend = pending[: per * gang_size]
        elif gate_pods:
            g_pend = pending[:gate_pods]
        else:
            # budget-sized over the full node axis: probe the serial rate,
            # then take as many pods as gate_budget_s affords
            from kubernetes_tpu.models.oracle import solve_serial
            probe = pending[:30]
            t0 = time.perf_counter()
            solve_serial(g_nodes, g_exist, probe, services, policy=policy,
                         gangs=True)
            rate = len(probe) / max(time.perf_counter() - t0, 1e-9)
            n_gate = max(200, min(len(pending), int(rate * gate_budget_s)))
            g_pend = pending[:n_gate]
            log(f"[{tag}] oracle probe {rate:.1f} pods/s -> gate sized to "
                f"{n_gate} pods x {len(g_nodes)} nodes "
                f"(~{gate_budget_s:.0f}s budget)")
        from kubernetes_tpu.models.batch_solver import solve
        from kubernetes_tpu.models.snapshot import encode_snapshot
        g_snap = encode_snapshot(g_nodes, g_exist, g_pend, services,
                                 policy=batch_policy)
        g_chosen, _ = solve(g_snap)
        res["gate"] = f"slice-oracle-{len(g_pend)}x{len(g_nodes)}"
    rate = check_equivalence(tag, g_snap, g_chosen, g_nodes, g_exist, g_pend,
                             services, policy=policy)
    if rate is None:
        return None
    res["serial_oracle_pods_per_s"] = round(rate, 1)

    if gang_groups:
        # full-scale all-or-nothing invariant: every group entirely placed
        # or entirely unplaced
        import numpy as np
        rid = snap.pod_rid[: len(pending)]
        ok = chosen_np[: len(pending)] >= 0
        whole = True
        for g in np.unique(rid[rid >= 0]):
            members = ok[rid == g]
            if members.any() != members.all():
                whole = False
                break
        if not whole:
            log(f"[{tag}] GANG INVARIANT FAILURE: partially placed group")
            return None
        placed = int(sum(1 for g in np.unique(rid[rid >= 0])
                         if ok[rid == g].all()))
        res["groups_placed"] = placed
        res["groups_total"] = gang_groups
        log(f"[{tag}] all-or-nothing invariant OK: "
            f"{placed}/{gang_groups} groups fully placed")

    log(f"[{tag}] wave {res['wave_s']:.3f}s over {res['runs']} runs "
        f"(p95 {res['wave_s_p95']:.3f} p99 {res['wave_s_p99']:.3f} "
        f"max {res['wave_s_max']:.3f}; path={res['path']}) "
        f"= encode {res['encode_s']:.3f} "
        f"+ device(transfer+solve+readback) {res['device_s']:.4f}; "
        f"{res['value']:.0f} pods/s; "
        f"scheduled {res['scheduled']}/{res['pods']}")
    return res


def run_mesh_config(tag, n_nodes, n_pods, pods_axis=1, gate_nodes=600,
                    gate_pods=600, runs=5):
    """Race the mesh-sharded GSPMD solve (parallel/mesh.sharded_program —
    the exact program kube-solverd's MeshExecutor dispatches) against the
    same program pinned to a 1x1 single-device submesh, on one wave at a
    node count above the mesh floor. Three gates, all hard: the two
    layouts must agree BITWISE on (chosen, scores); the decisions must
    match the slice serial oracle; and padding indices must never escape
    the real node range. ``value`` is the WINNING layout's pods/s — on a
    CPU sub-mesh the single-device layout usually wins (the measured
    crossover MeshExecutor's auto dispatch encodes); on real multi-chip
    the sharded layout is the capacity path. Both rates are recorded so
    the record shows the crossover, not just the winner."""
    import jax

    if jax.device_count() <= 1:
        log(f"[{tag}] needs >1 device (have {jax.device_count()}; set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N); skipping")
        return None
    import numpy as np

    from kubernetes_tpu.models.batch_solver import snapshot_to_host_inputs
    from kubernetes_tpu.models.snapshot import encode_snapshot
    from kubernetes_tpu.parallel import mesh as pm

    log(f"[{tag}] building {n_pods} pods x {n_nodes} nodes "
        f"(mesh {jax.device_count() // pods_axis} node-shards x "
        f"{pods_axis} pods)")
    nodes, existing, pending, services = build_cluster(n_nodes, n_pods)
    snap = encode_snapshot(nodes, existing, pending, services)
    inp = snapshot_to_host_inputs(snap)
    full = pm.make_mesh(pods_axis=pods_axis)
    single = pm.make_mesh(jax.devices()[:1], pods_axis=1)

    def timed(mesh):
        def once():
            t0 = time.perf_counter()
            out = pm.solve_sharded(inp, mesh, pol=snap.policy,
                                   gangs=snap.has_gangs,
                                   prefer_kernel=False)
            return out, time.perf_counter() - t0
        out, _cold = once()  # compile + first placement, untimed
        times = []
        for _ in range(runs):
            out, dt = once()
            times.append(dt)
        times.sort()
        return out, times[len(times) // 2]

    (sh_chosen, sh_scores), sharded_s = timed(full)
    (sg_chosen, sg_scores), single_s = timed(single)
    if not (np.array_equal(sh_chosen, sg_chosen)
            and np.array_equal(sh_scores, sg_scores)):
        n_div = int((sh_chosen != sg_chosen).sum())
        log(f"[{tag}] LAYOUT PARITY FAILURE: sharded != single-device "
            f"({n_div}/{len(sh_chosen)} decisions diverge)")
        return None
    if sh_chosen.max(initial=-1) >= n_nodes:
        log(f"[{tag}] PADDING ESCAPE: decision index "
            f"{int(sh_chosen.max())} >= {n_nodes}")
        return None

    # slice serial-oracle gate, same derivation as run_solver_config
    g_nodes = nodes[:gate_nodes]
    keep = {n.metadata.name for n in g_nodes}
    g_exist = [p for p in existing if p.status.host in keep]
    g_pend = pending[:gate_pods]
    g_snap = encode_snapshot(g_nodes, g_exist, g_pend, services)
    g_chosen, _ = pm.solve_sharded(snapshot_to_host_inputs(g_snap), full,
                                   pol=g_snap.policy,
                                   gangs=g_snap.has_gangs,
                                   prefer_kernel=False)
    rate = check_equivalence(tag, g_snap, g_chosen, g_nodes, g_exist,
                             g_pend, services)
    if rate is None:
        return None

    report = pm.shard_memory_report(inp, full)
    winner = "shard" if sharded_s < single_s else "single"
    best_s = min(sharded_s, single_s)
    res = {
        "pods": n_pods, "nodes": n_nodes,
        "devices": jax.device_count(),
        "pods_axis": pods_axis,
        "node_shards": int(full.shape["nodes"]),
        "sharded_wave_s": round(sharded_s, 4),
        "single_wave_s": round(single_s, 4),
        "winner": winner,
        "value": round(n_pods / best_s, 1),
        "sharded_pods_per_s": round(n_pods / sharded_s, 1),
        "single_pods_per_s": round(n_pods / single_s, 1),
        "speedup": round(single_s / sharded_s, 3),
        "layout_parity": "bitwise-identical",
        "gate": f"slice-oracle-{len(g_pend)}x{len(g_nodes)}",
        "serial_oracle_pods_per_s": round(rate, 1),
        "shard_bytes_per_device": report["total_bytes_per_device"],
        "runs": runs,
    }
    log(f"[{tag}] sharded {sharded_s:.3f}s vs single-device "
        f"{single_s:.3f}s per wave -> {winner} wins "
        f"({res['value']:.0f} pods/s); layouts bitwise identical; "
        f"{report['total_bytes_per_device'] >> 20} MiB/device sharded")
    return res


def run_churn_config(tag, n_nodes, n_pods, rate_pods_per_s, wave_size=1024,
                     solver_addr=""):
    """Churn replay through the REAL BatchScheduler: in-process apiserver,
    reflectors, FIFO, incremental encoder, Binding writes — pods offered at
    a fixed rate, sustained bind throughput measured. With ``solver_addr``
    the waves solve on a shared kube-solverd daemon (cmd/solverd) instead
    of in-process — the record then carries the remote/fallback wave
    split so a silently-down daemon can't pass as a solverd measurement."""
    import threading

    from kubernetes_tpu.api import types as api
    from kubernetes_tpu.api.quantity import Quantity
    from kubernetes_tpu.apiserver.master import Master
    from kubernetes_tpu.client.client import Client, InProcessTransport
    from kubernetes_tpu.scheduler.driver import ConfigFactory
    from kubernetes_tpu.scheduler.tpu_batch import BatchScheduler

    log(f"[{tag}] {n_pods} pods at {rate_pods_per_s}/s onto {n_nodes} nodes "
        f"through the live scheduler stack"
        + (f" (solverd at {solver_addr})" if solver_addr else ""))
    m = Master()
    client = Client(InProcessTransport(m))
    for i in range(n_nodes):
        client.nodes().create(api.Node(
            metadata=api.ObjectMeta(name=f"node-{i:05d}"),
            spec=api.NodeSpec(capacity={"cpu": Quantity("64"),
                                        "memory": Quantity("256Gi")})))
    factory = ConfigFactory(client)
    config = factory.create(solver_addr=solver_addr)
    sched = BatchScheduler(config, factory, client, wave_size=wave_size,
                           wave_linger_s=0.1).run()
    try:
        time.sleep(0.5)  # reflectors sync

        def feed(prefix, count):
            for i in range(count):
                client.pods().create(api.Pod(
                    metadata=api.ObjectMeta(name=f"{prefix}-{i:06d}",
                                            namespace="default"),
                    spec=api.PodSpec(containers=[api.Container(
                        name="c", image="img",
                        resources=api.ResourceRequirements(limits={
                            "cpu": Quantity("100m"),
                            "memory": Quantity("128Mi")}))])))

        def bound_total():
            # the scheduler's own assigned-pods reflector store: O(1)-ish
            # len, no full-list serialization stealing the GIL from the
            # feeder and the waves
            return len(factory.scheduled_pods.list())

        def wait_bound(total, timeout=120.0):
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                if bound_total() >= total:
                    return True
                time.sleep(0.05)
            return False

        # warmup: populate the incremental encoder's resident planes and
        # pre-compile EVERY pow-2 wave bucket the timed phase can hit —
        # a bucket first seen mid-run costs a 2-3s compile that stalls
        # the feeder for seconds. Walk every power of two (size //= 2),
        # 2 rounds so split waves cover stragglers. Steady state is what
        # the 1k pods/s contract is about; cold compiles are a
        # once-per-shape cost.
        warm = 0
        for round_ in range(2):
            size = wave_size
            while size >= 1:
                feed(f"warm{round_}x{size}", size)
                warm += size
                if not wait_bound(warm):
                    log(f"[{tag}] CHURN FAILURE: warmup bucket {size} "
                        f"(round {round_}) did not bind within 120s "
                        f"({bound_total()}/{warm} bound)")
                    return None
                size //= 2
        log(f"[{tag}] warmup: {warm} pods bound across wave buckets; "
            f"starting the clock")
        # The load generator is multi-threaded like the reference's master
        # churn test ("5 threads x short-lived pods",
        # test/e2e/density.go:206-215): a single paced feeder thread gets
        # one GIL share against the watch pumps and wave loop and tops out
        # well under the offered-rate target; F feeders each pace at
        # rate/F and their aggregate tracks the contract.
        FEEDERS = 4
        behind = [0.0] * FEEDERS
        counts = [0] * FEEDERS

        def paced_feed(f_idx: int, count: int, rate: float):
            interval = 1.0 / rate
            next_t = time.perf_counter()
            for i in range(count):
                client.pods().create(api.Pod(
                    metadata=api.ObjectMeta(
                        name=f"churn-{f_idx}-{i:06d}",
                        namespace="default"),
                    spec=api.PodSpec(containers=[api.Container(
                        name="c", image="img",
                        resources=api.ResourceRequirements(limits={
                            "cpu": Quantity("100m"),
                            "memory": Quantity("128Mi")}))])))
                counts[f_idx] += 1
                next_t += interval
                now = time.perf_counter()
                behind[f_idx] = max(behind[f_idx], now - next_t)
                if next_t > now:
                    time.sleep(next_t - now)

        per = n_pods // FEEDERS
        split = [per + (1 if f < n_pods % FEEDERS else 0)
                 for f in range(FEEDERS)]
        t_start = time.perf_counter()
        threads = [threading.Thread(
            target=paced_feed, args=(f, split[f], rate_pods_per_s / FEEDERS),
            daemon=True) for f in range(FEEDERS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        feed_s = time.perf_counter() - t_start
        created = sum(counts)
        behind_max = max(behind)
        # drain: wait for every timed pod to bind
        deadline = time.monotonic() + 60.0
        bound = 0
        while time.monotonic() < deadline:
            bound = bound_total() - warm
            if bound >= n_pods:
                break
            time.sleep(0.05)
        total_s = time.perf_counter() - t_start
        value = bound / total_s
        offered = created / feed_s
        log(f"[{tag}] offered {offered:.0f} pods/s, bound {bound}/{n_pods} "
            f"in {total_s:.2f}s -> sustained {value:.0f} pods/s "
            f"(feeder fell behind by at most {behind_max:.2f}s)")
        if bound < n_pods:
            log(f"[{tag}] CHURN FAILURE: {n_pods - bound} pods never bound")
            return None

        # saturation phase: same stack, feeders unpaced — the system's
        # max bind throughput, which must DOMINATE the contract rate
        # (sustaining 1k/s with zero headroom is not the same claim)
        sat_base = bound_total()
        sat_t0 = time.perf_counter()
        sat_threads = [threading.Thread(
            target=feed, args=(f"sat{f}", n_pods // FEEDERS), daemon=True)
            for f in range(FEEDERS)]
        for t in sat_threads:
            t.start()
        for t in sat_threads:
            t.join()
        sat_feed_s = time.perf_counter() - sat_t0
        sat_total = (n_pods // FEEDERS) * FEEDERS
        deadline = time.monotonic() + 60.0
        sat_bound = 0
        while time.monotonic() < deadline:
            sat_bound = bound_total() - sat_base
            if sat_bound >= sat_total:
                break
            time.sleep(0.05)
        sat_s = time.perf_counter() - sat_t0
        sat_value = sat_bound / sat_s
        log(f"[{tag}] saturation: offered {sat_total / sat_feed_s:.0f} "
            f"pods/s unpaced -> sustained {sat_value:.0f} pods/s")
        rec = {
            "pods": n_pods, "nodes": n_nodes,
            "value": round(value, 1), "unit": "pods/s",
            "offered_pods_per_s": round(offered, 1),
            "total_s": round(total_s, 2),
            "gate": "all-bound-via-live-stack",
        }
        if solver_addr:
            rs = sched.solver
            rec["solver_addr"] = solver_addr
            rec["solverd_remote_waves"] = rs.remote_waves
            rec["solverd_fallback_waves"] = rs.fallback_waves
            rec["solverd_busy_waves"] = rs.busy_waves
        if sat_bound >= sat_total:
            rec["saturation_pods_per_s"] = round(sat_value, 1)
            rec["saturation_offered_pods_per_s"] = round(
                sat_total / sat_feed_s, 1)
        return rec
    finally:
        sched.stop()
        factory.stop()


def _parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(prog="bench.py")
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes + force CPU (CI / laptops); the "
                         "only way to run without a TPU")
    ap.add_argument("--pods", type=int, default=None,
                    help="north-star pending pods override")
    ap.add_argument("--nodes", type=int, default=None,
                    help="north-star node count override")
    ap.add_argument("--configs", default="all",
                    help="comma list: north_star,basic,affinity,binpack3,"
                         "gang,churn (default all)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of the north-star "
                         "solve into DIR")
    ap.add_argument("--runs", type=int, default=None,
                    help="timed steady-state waves per config (default: 30 "
                         "on the TPU, 5 for --smoke)")
    ap.add_argument("--solver-addr", "--solver_addr", default="",
                    help="HOST:PORT of a running kube-solverd daemon "
                         "(cmd/solverd); the churn config then solves its "
                         "waves there instead of in-process. The "
                         "multi-process analog is hack/churn_mp.py "
                         "--solverd, which spawns the daemon itself.")
    ap.add_argument("--detail-out", "--detail_out", default=None,
                    help="full-record sidecar (runs_s arrays, router "
                         "calibration); default BENCH_detail.json next "
                         "to bench.py. The stdout line stays < 1.5 KB")
    return ap


def main(argv=None) -> int:
    args = _parser().parse_args(argv)

    import jax

    if args.smoke:
        jax.config.update("jax_platforms", "cpu")
    else:
        # expose the host CPU backend BESIDE the accelerator (first platform
        # stays the default) so the wave router can run dispatch-bound waves
        # on the host — see models/batch_solver.WaveRouter
        plats = os.environ.get("JAX_PLATFORMS", "")
        if plats and "cpu" not in plats.split(","):
            jax.config.update("jax_platforms", plats + ",cpu")
    try:
        devices = jax.devices()
    except RuntimeError as e:      # no backend could be initialised
        print(_error_record(f"JAX backend init failed: {e}"))
        return 1
    device = {"platform": devices[0].platform,
              "device_kind": devices[0].device_kind,
              "device_count": len(devices)}
    log(f"[bench] device: {device}")
    if device["platform"] != "tpu" and not args.smoke:
        # a measurement path that finds no chip fails: a CPU time must
        # never stand under a device metric's name
        print(_error_record("no TPU found; only --smoke runs on the CPU "
                            "backend", device))
        return 1

    # warm start: persistent XLA compile cache + router calibrations
    # (KTPU_WARM_START=off for fresh-cold numbers)
    from kubernetes_tpu.util import warmstart
    warmstart.enable()

    s = args.smoke
    runs = args.runs or (5 if s else 30)
    known = {"north_star", "basic", "affinity", "binpack3", "gang", "churn",
             "mesh", "priority"}
    if args.configs != "all":
        want = set(args.configs.split(","))
    else:
        want = set(known)
        if len(devices) <= 1:
            # the mesh config races two device layouts; without a second
            # device there is nothing to race (run under XLA_FLAGS=
            # --xla_force_host_platform_device_count=N to include it)
            want.discard("mesh")
    detail_path = args.detail_out or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_detail.json")
    unknown = want - known
    if unknown:
        log(f"[bench] unknown --configs: {sorted(unknown)}; "
            f"known: {sorted(known)}")
        print(_error_record(f"unknown configs: {sorted(unknown)}", device))
        return 2
    configs = {}
    failed = []

    # anti-affinity policy: shared definition (see affinity_policy)
    aff_policy = affinity_policy()

    def build_record():
        """One shape for every emission: success, cumulative partial
        (missing configs listed under "partial"), and failure ("error")."""
        primary = configs.get("north_star") or next(iter(configs.values()),
                                                    None)
        rec = {
            "metric": "pods_scheduled_per_sec" if primary is None else
                      f"pods_scheduled_per_sec_{primary['pods']}pods_"
                      f"{primary['nodes']}nodes",
            "value": 0.0 if primary is None else primary["value"],
            "unit": "pods/s",
            "vs_baseline": 0.0 if primary is None else
                           round(primary["value"] / BASELINE_PODS_PER_S, 3),
            "timing": TIMING_DESC,
            # the device this record's numbers were taken on: a device
            # metric without it is not one
            **device,
            "configs": configs,
        }
        if failed:
            rec["value"], rec["vs_baseline"] = 0.0, 0.0
            rec["error"] = f"failed configs: {failed}"
        # independent of "error": never-run configs stay visible even on a
        # failure record
        if want - set(configs) - set(failed):
            rec["partial"] = sorted(want - set(configs) - set(failed))
        return rec

    def run(tag, fn, *a, **kw):
        if tag not in want:
            return
        r = fn(tag, *a, **kw)
        if r is None:
            failed.append(tag)
        else:
            configs[tag] = r
        # Emit the cumulative record after EVERY config — success or
        # failure — so if the run later crashes or is cut, the last JSON
        # line on stdout is the newest truth (a failure record supersedes
        # the pre-failure partials). Stdout carries the COMPACT
        # form (the <1.5 KB contract); the full record lands in the
        # detail sidecar.
        if configs or failed:
            rec = build_record()
            _write_detail(detail_path, rec)
            print(_compact_record(rec,
                                  detail_name=os.path.basename(detail_path)),
                  flush=True)

    # north star: budget-sized oracle gate over the FULL node axis (a
    # complete 10k x 5k serial oracle is ~20min; FULLGATE_r03.json records
    # the out-of-band full-scale equivalence run)
    # full shapes come from FULL_SHAPES — the ONE definition shared with
    # hack/fullgate.py, so the out-of-band gate always certifies exactly
    # the config this matrix runs
    ns_nodes, ns_pods, _ = FULL_SHAPES["north_star"]
    run("north_star", run_solver_config,
        args.nodes or (100 if s else ns_nodes),
        args.pods or (500 if s else ns_pods),
        full_gate=s, profile=args.profile, runs=runs)
    b_nodes, b_pods, _ = FULL_SHAPES["basic"]
    run("basic", run_solver_config,
        50 if s else b_nodes, 100 if s else b_pods, full_gate=True,
        runs=runs)
    a_nodes, a_pods, _ = FULL_SHAPES["affinity"]
    run("affinity", run_solver_config,
        100 if s else a_nodes, 200 if s else a_pods,
        gate_nodes=100 if s else 600, gate_pods=200 if s else 600,
        policy=aff_policy, runs=runs)
    p3_nodes, p3_pods, p3_kw = FULL_SHAPES["binpack3"]
    run("binpack3", run_solver_config,
        100 if s else p3_nodes, 300 if s else p3_pods,
        gate_nodes=100 if s else 600, gate_pods=300 if s else 600,
        runs=runs, **p3_kw)
    g_nodes, g_pods, g_kw = FULL_SHAPES["gang"]
    run("gang", run_solver_config,
        100 if s else g_nodes, g_pods,
        gate_nodes=50 if s else 200, gate_pods=160 if s else 400,
        runs=runs,
        **({"gang_groups": 20, "gang_size": 8} if s else g_kw))
    m_nodes, m_pods, _ = FULL_SHAPES["mesh"]
    run("mesh", run_mesh_config,
        256 if s else m_nodes, 128 if s else m_pods,
        gate_nodes=100 if s else 600, gate_pods=100 if s else 600,
        runs=2 if s else 5)
    pr_nodes, pr_pods, _ = FULL_SHAPES["priority"]
    run("priority", run_priority_config,
        50 if s else pr_nodes, 60 if s else pr_pods,
        gate_nodes=25 if s else 150, gate_pods=60 if s else 200,
        runs=runs)
    run("churn", run_churn_config,
        20 if s else 500, 300 if s else 8_000,
        rate_pods_per_s=300 if s else 1_000,
        solver_addr=args.solver_addr)

    record = build_record()
    if not configs and not failed:
        record["error"] = "no configs ran"
    _write_detail(detail_path, record)
    print(_compact_record(record,
                          detail_name=os.path.basename(detail_path)))
    return 1 if (failed or not configs) else 0


if __name__ == "__main__":
    sys.exit(main())
