"""Churn at contract rate through the MULTI-PROCESS topology.

The bench's in-process churn config puts the feeder, the apiserver, the
watch pumps, and the scheduler wave loop in one Python process — every
thread shares one GIL, which caps the offered rate well below what the
components can individually sustain. The reference never runs that way:
each component is its own process talking HTTP (DESIGN.md:40). This
harness reproduces that deployment: an apiserver process, a kube-scheduler
process (--algorithm tpu-batch), and N feeder processes offering pods at
a paced aggregate rate over real HTTP. The result is recorded for the
round (CHURN_MP_r{N}.json).

Usage:
  python hack/churn_mp.py [--pods 6000] [--rate 1000] [--nodes 500]
                          [--feeders 4] [--out FILE]
  (internal) python hack/churn_mp.py --_feed PREFIX COUNT RATE MASTER [LOG]
"""

from __future__ import annotations

import argparse
import json
import mmap
import os
import re
import struct
import subprocess
import sys
import tempfile
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PY = sys.executable
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# APPEND to the ambient PYTHONPATH: it may carry backend plugins
ENV = dict(os.environ, PYTHONPATH=_REPO + (
    os.pathsep + os.environ["PYTHONPATH"]
    if os.environ.get("PYTHONPATH") else ""))


def cpu_env() -> dict:
    """Child env pinned to the CPU backend: a chip belongs to one process
    at a time, so every child that is not the solver must stay off it."""
    return dict(ENV, JAX_PLATFORMS="cpu")


def chip_child(platform: str, solverd: bool, schedulers: int):
    """The ONE child that inherits the ambient (chip) environment under
    ``--platform ambient``: the shared daemon when there is one, else the
    single scheduler. None under ``--platform cpu``. A second process
    that reaches for the chip fails or hangs, so several in-process
    schedulers cannot share it."""
    if platform != "ambient":
        return None
    if solverd:
        return "solverd"
    if schedulers > 1:
        raise ValueError("--platform ambient gives the chip to one process: "
                         "--schedulers > 1 needs --solverd")
    return "scheduler0"


def child_env_for(name: str, owner) -> dict:
    return dict(ENV) if name == owner else cpu_env()


def _pod_template(priority_class: str = "") -> str:
    spec = {"containers": [{
        "name": "c", "image": "img",
        "resources": {"limits": {"cpu": "100m",
                                 "memory": "128Mi"}}}]}
    if priority_class:
        # kube-preempt: the apiserver's PriorityDefault admission resolves
        # the class into spec.priority at create — feeders ship the NAME
        spec["priorityClassName"] = priority_class
    return json.dumps({
        "kind": "Pod", "apiVersion": "v1",
        "metadata": {"name": "@@NAME@@", "namespace": "default"},
        "spec": spec})


_POD_TEMPLATE = _pod_template()
_POD_PATH = "/api/v1/namespaces/default/pods"


def _render_request(prefix: str, i: int, priority_class: str = "") -> bytes:
    tmpl = _pod_template(priority_class) if priority_class \
        else _POD_TEMPLATE
    head, tail = tmpl.split("@@NAME@@")
    body = f"{head}{prefix}-{i:06d}{tail}".encode()
    return (b"POST " + _POD_PATH.encode() + b" HTTP/1.1\r\n"
            b"Host: a\r\nContent-Type: application/json\r\n"
            b"Content-Length: " + str(len(body)).encode() +
            b"\r\n\r\n" + body)


def render_replay(prefix: str, count: int, path: str,
                  priority_class: str = "") -> str:
    """Pre-serialize a feeder's whole request stream to a replay log:
    ``path`` holds COUNT raw pipelined HTTP requests back-to-back and
    ``path + ".idx"`` the little-endian u32 offsets (count+1 entries).
    The paced send loop then costs one mmap slice per pod — ~0 CPU —
    instead of a JSON render + f-string + bytes build per pod, which at
    full shape was enough construction work to starve the offered rate
    below the contract (CHURN_MP_r05_fullshape: 727/s offered of the
    1,000 target)."""
    offs = [0]
    with open(path, "wb") as fh:
        for i in range(count):
            req = _render_request(prefix, i, priority_class)
            fh.write(req)
            offs.append(offs[-1] + len(req))
    with open(path + ".idx", "wb") as fh:
        fh.write(struct.pack(f"<{len(offs)}I", *offs))
    return path


def feed(prefix: str, count: int, rate: float, master: str,
         depth: int = 32, replay: str = "", priority_class: str = "") -> int:
    """Paced feeder (one process). Prints one JSON line when done.

    Offers pods over a raw keep-alive socket — a load generator must be
    cheaper than the server it measures (the kubemark principle); the
    stdlib http.client's per-response email-parser alone cost ~0.1ms/req
    of the shared one-core budget. With ``replay`` the requests come
    pre-serialized from a replay log (render_replay) and the send loop is
    pure mmap-slice + sendall; without it they are rendered live (warmup
    path). Requests are PIPELINED up to ``depth`` in flight: the send
    side paces at the target rate while a reader thread drains status
    lines, so the offered rate tracks the contract instead of the
    server's per-request latency.

    kube-chaos restart transparency (docs/design/ha.md): the feeder must
    never surface a component respawn as a failed run. Responses arrive
    in request order on one pipelined connection, so the acked prefix is
    exact — on a connection death or a 5xx (an apiserver worker or
    kube-store dying mid-call), the feeder reconnects and RESUMES from
    the first unacked request. Re-sent creates that had in fact applied
    answer 409; those are tolerated (and counted) only once the feeder
    is in recovery — a 409 or 4xx on the first pass is still a real bug
    and aborts. A recovery that makes no progress for 90 s aborts too:
    retrying forever would hide a dead control plane.

    kube-fairshed backpressure: a 429 is RETRY, never poison — the
    server refused the create before executing it (nothing applied), so
    the feeder honors the response's Retry-After (sleeping the server's
    measured-drain hint), reconnects, and resumes from the acked prefix
    exactly like a crash recovery. Requests pipelined PAST the 429 may
    have landed (the server keeps serving the connection), so the 409
    tolerance window covers the resend, same as the 5xx path. Counted
    in ``retried_429``; under --overload this is the designed steady
    state, not an anomaly."""
    import socket
    import threading
    import urllib.parse

    u = urllib.parse.urlparse(master)
    log_mm = idx = None
    if replay:
        with open(replay + ".idx", "rb") as fh:
            raw = fh.read()
        idx = struct.unpack(f"<{len(raw) // 4}I", raw)
        if len(idx) != count + 1:
            print(json.dumps({"error": f"replay log {replay} holds "
                              f"{len(idx) - 1} requests, need {count}"}),
                  flush=True)
            return 1
        log_fh = open(replay, "rb")
        log_mm = mmap.mmap(log_fh.fileno(), 0, access=mmap.ACCESS_READ)
        log_mv = memoryview(log_mm)

    status_re = re.compile(rb"HTTP/1\.1 (\d{3})")
    retry_after_re = re.compile(rb"Retry-After: (\d+)")
    acked = [0]         # responses accepted, == the acked request prefix
    bad = []            # fatal status lines / errors
    # 409s are tolerated ONLY for request indices below this high-water
    # mark — exactly the requests a broken stream forced us to re-send.
    # A blanket "recovering" latch would let a first-pass duplicate-
    # create bug late in the run masquerade as delivery.
    tolerate_below = [0]
    stats = {"reconnects": 0, "retried_conflicts": 0, "retried_5xx": 0,
             "retried_429": 0}
    # Retry-After seconds to honor before the next reconnect (a 429'd
    # stream); capped so a misbehaving hint can't wedge the feeder
    resume_after = [0.0]
    lock = threading.Lock()

    interval = 1.0 / rate
    t0 = time.perf_counter()
    next_t = t0
    behind_max = 0.0
    stalled_since = None  # wall deadline for zero-progress recovery

    while acked[0] < count and not bad:
        base = acked[0]
        try:
            sock = socket.create_connection((u.hostname, u.port),
                                            timeout=5.0)
        except OSError as e:
            now = time.monotonic()
            if stalled_since is None:
                stalled_since = now
            if now - stalled_since > 90.0:
                bad.append(f"connect: {e}")
                break
            time.sleep(0.5)
            continue
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        conn_down = threading.Event()

        def reader(sock=sock, conn_down=conn_down, base=base):
            buf = b""
            accepted = 0   # contiguous accepted responses on THIS conn
            while acked[0] < count:
                try:
                    chunk = sock.recv(1 << 16)
                except OSError:
                    break
                if not chunk:
                    break
                buf += chunk
                # fast path: a full recv of nothing but 2xx statuses (the
                # steady state) is two substring counts + one rfind, no
                # regex and no per-response Match objects. Classification
                # needs only the FIRST status digit, so a trailing
                # "HTTP/1.1 2" with its last digits still in flight counts
                # now and the cut point keeps the leftover digits from
                # ever re-matching. Any non-2xx (or a marker cut before
                # its first digit) falls through to the exact loop below.
                n_status = buf.count(b"HTTP/1.1 ")
                if n_status and buf.count(b"HTTP/1.1 2") == n_status:
                    accepted += n_status
                    acked[0] = min(count, base + accepted)
                    buf = buf[buf.rfind(b"HTTP/1.1 2") + 10:]
                    if len(buf) > 16:
                        buf = buf[-16:]
                    continue
                last_end, poison = 0, False
                for m in status_re.finditer(buf):
                    code = m.group(1)
                    # responses arrive in request order on the pipelined
                    # connection: this status answers request base+accepted
                    idx = base + accepted
                    if code[:1] == b"2":
                        accepted += 1
                        last_end = m.end()
                        continue
                    if code == b"409" and idx < tolerate_below[0]:
                        # a RE-SENT create that had applied before the
                        # outage: the pod exists — counts as delivered.
                        # A 409 at or past the re-send high-water mark is
                        # a first-pass duplicate — a real bug, fatal.
                        with lock:
                            stats["retried_conflicts"] += 1
                        accepted += 1
                        last_end = m.end()
                        continue
                    if code == b"429":
                        # kube-fairshed shed: the server refused this
                        # create BEFORE executing it — retry, never
                        # poison. Honor its Retry-After (the headers
                        # follow the status line in this same buffer;
                        # a split-across-chunks header falls back to
                        # 1 s), then resume from the acked prefix.
                        m2 = retry_after_re.search(buf, m.end())
                        with lock:
                            stats["retried_429"] += 1
                            resume_after[0] = min(
                                30.0, float(m2.group(1)) if m2 else 1.0)
                        poison = True
                        break
                    if code[:1] == b"5":
                        # a component died mid-call (e.g. the store
                        # behind the apiserver): poison this stream at
                        # the failed request and resume from it
                        with lock:
                            stats["retried_5xx"] += 1
                        poison = True
                        break
                    with lock:
                        bad.append(code.decode("ascii"))
                    poison = True
                    break
                acked[0] = min(count, base + accepted)
                if poison:
                    break
                # drop consumed bytes; keep a tail short enough to never
                # lose a status marker split across chunks
                buf = buf[last_end:]
                if len(buf) > 16:
                    buf = buf[-16:]
            conn_down.set()
            try:
                sock.close()
            except OSError:
                pass

        rt = threading.Thread(target=reader, daemon=True)
        rt.start()
        # Replay requests are CONTIGUOUS in the log, so a span of them is
        # one mmap slice — one sendall (one syscall, zero copies) covers
        # up to span_max requests instead of one each. The span never
        # exceeds half the pipeline depth (the reader keeps draining
        # while we sleep) and pacing charges the whole span at once:
        # bursts of ≤span_max at the wire level, same offered rate.
        span_max = max(1, min(32, depth // 2)) if log_mm is not None else 1
        i = base
        while i < count and not bad:
            while i - acked[0] >= depth and not bad \
                    and not conn_down.is_set():
                time.sleep(0.0005)
            if bad or conn_down.is_set():
                break
            if log_mm is not None:
                j = min(count, i + span_max, acked[0] + depth)
                if j <= i:       # acked[0] only grows; belt and braces
                    j = i + 1
                req = log_mv[idx[i]:idx[j]]
            else:
                j = i + 1
                req = _render_request(prefix, i, priority_class)
            try:
                sock.sendall(req)
            except OSError:
                break
            next_t += interval * (j - i)
            i = j
            now = time.perf_counter()
            behind_max = max(behind_max, now - next_t)
            if next_t > now:
                time.sleep(next_t - now)
        if i >= count:
            # everything sent on this connection: wait for the acked
            # prefix to drain (or the connection to die — then resume)
            deadline = time.monotonic() + 120.0
            while acked[0] < count and not conn_down.is_set() \
                    and time.monotonic() < deadline:
                time.sleep(0.005)
        try:
            sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            sock.close()
        except OSError:
            pass
        rt.join(timeout=5.0)
        if acked[0] >= count or bad:
            break
        # the stream ended short (reconnect, poison, drain timeout, send
        # error): resume from the acked prefix on a fresh connection;
        # everything sent on THIS conn (up to index i) may have applied,
        # so 409s below i are tolerable on the resend
        tolerate_below[0] = max(tolerate_below[0], i)
        with lock:
            stats["reconnects"] += 1
            hold = resume_after[0]
            resume_after[0] = 0.0
        if hold > 0:
            # a 429'd stream: honor the server's Retry-After before
            # resuming — the backpressure loop that keeps the admitted
            # rate at what the control plane actually drains
            time.sleep(hold)
        if acked[0] > base:
            stalled_since = None       # progress was made
        elif stalled_since is None:
            stalled_since = time.monotonic()
        elif time.monotonic() - stalled_since > 90.0:
            bad.append(f"no progress past {acked[0]}/{count} for 90s")
            break

    dt = time.perf_counter() - t0
    if bad:
        print(json.dumps({"error": f"create failed: {bad[:3]}",
                          "created": acked[0], **stats}), flush=True)
        return 1
    if acked[0] < count:
        print(json.dumps({"error": f"server acknowledged only {acked[0]}"
                          f"/{count} creates", "created": acked[0],
                          **stats}), flush=True)
        return 1
    print(json.dumps({"created": count, "seconds": round(dt, 3),
                      "rate": round(count / dt, 1),
                      "behind_max_s": round(behind_max, 3),
                      # self-reported: /proc is gone by the time the
                      # parent aggregates the per-stage CPU budget
                      "cpu_s": round(time.process_time(), 3),
                      **stats}), flush=True)
    return 0


def _scrape_wave_raw(port: int) -> dict:
    """-> {which: (sorted [(le, cumcount)], sum, count)} from /metrics."""
    raw = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
    out = {}
    for which in ("encode", "solve", "commit"):
        base = f"scheduler_wave_{which}_seconds"
        buckets, total, count = [], 0.0, 0.0
        for line in raw.splitlines():
            if line.startswith(base + "_bucket"):
                le = line.split('le="', 1)[1].split('"', 1)[0]
                buckets.append((float("inf") if le == "+Inf" else float(le),
                                float(line.rsplit(None, 1)[1])))
            elif line.startswith(base + "_sum"):
                total = float(line.rsplit(None, 1)[1])
            elif line.startswith(base + "_count"):
                count = float(line.rsplit(None, 1)[1])
        out[which] = (sorted(buckets), total, count)
    return out


def _scrape_wave_programs(port: int) -> dict:
    """{"<program>@<platform>": waves} one process solved itself
    (models/batch_solver.wave_programs). In a scheduler that has a shared
    daemon these are the waves RemoteSolver solved in-process instead —
    fallbacks and BUSY bounces — on that scheduler's own backend."""
    raw = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
    out = {}
    for line in raw.splitlines():
        if line.startswith("solver_wave_program_total{"):
            program = line.split('program="', 1)[1].split('"', 1)[0]
            platform = line.split('platform="', 1)[1].split('"', 1)[0]
            out[f"{program}@{platform}"] = int(float(line.rsplit(None, 1)[1]))
    return out


def _scrape_slipstream(port: int) -> dict:
    """kube-slipstream evidence from one scheduler's (or solverd's)
    /metrics: journal-replay vs full encoder resyncs (by reason), the
    prewarm compile counters + readiness gauge, and the worst single
    wave stall."""
    raw = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
    out = {"resync_replay": 0, "resync_full": 0,
           "resync_full_reasons": {}, "prewarm_compiles": 0,
           "prewarm_ready": 0, "stall_max_s": 0.0}
    for line in raw.splitlines():
        if line.startswith("encoder_resync_full_total{"):
            reason = line.split('reason="', 1)[1].split('"', 1)[0]
            v = int(float(line.rsplit(None, 1)[1]))
            out["resync_full_reasons"][reason] = v
            out["resync_full"] += v
        elif line.startswith("encoder_resync_replay_total "):
            out["resync_replay"] = int(float(line.rsplit(None, 1)[1]))
        elif line.startswith("compile_prewarm_total "):
            out["prewarm_compiles"] = int(float(line.rsplit(None, 1)[1]))
        elif line.startswith("compile_prewarm_ready "):
            out["prewarm_ready"] = int(float(line.rsplit(None, 1)[1]))
        elif line.startswith("scheduler_wave_stall_max_seconds "):
            out["stall_max_s"] = float(line.rsplit(None, 1)[1])
    return out


def _scrape_solverd(port: int) -> dict:
    """Coalescing + delta-wire evidence from the daemon's /metrics:
    device solves vs waves served -> the measured coalesce factor;
    solverd_delta_* -> delta hit rate, resyncs, bytes shipped vs saved."""
    raw = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
    vals = {}
    resyncs = 0.0
    for line in raw.splitlines():
        if line.startswith("solverd_delta_resyncs_total{"):
            resyncs += float(line.rsplit(None, 1)[1])
            continue
        for key in ("solverd_device_solves_total",
                    "solverd_coalesced_waves_total",
                    "solverd_delta_hits_total",
                    "solverd_delta_full_frames_total",
                    "solverd_delta_bytes_shipped_total",
                    "solverd_delta_bytes_saved_total"):
            if line.startswith(key + " "):
                vals[key] = float(line.rsplit(None, 1)[1])
    solves = vals.get("solverd_device_solves_total", 0.0)
    waves = vals.get("solverd_coalesced_waves_total", 0.0)
    out = {"device_solves": int(solves), "waves_served": int(waves)}
    if solves:
        out["coalesce_factor"] = round(waves / solves, 2)
    hits = vals.get("solverd_delta_hits_total", 0.0)
    fulls = vals.get("solverd_delta_full_frames_total", 0.0)
    out["delta_hits"] = int(hits)
    out["delta_full_frames"] = int(fulls)
    out["delta_resyncs"] = int(resyncs)
    out["delta_hit_rate"] = round(hits / (hits + fulls), 3) \
        if hits + fulls else 0.0
    out["delta_bytes_shipped"] = int(
        vals.get("solverd_delta_bytes_shipped_total", 0.0))
    out["delta_bytes_saved"] = int(
        vals.get("solverd_delta_bytes_saved_total", 0.0))
    mesh = _scrape_solverd_mesh(raw)
    if mesh is not None:
        out["mesh"] = mesh
    return out


def _scrape_solverd_mesh(raw: str):
    """The solverd_mesh_* family (solver/mesh_exec.MeshExecutor): mesh
    topology, device-resident plane traffic (delta scatters vs resharding
    re-establishes), per-device shard footprint, the mesh-vs-single solve
    quantiles, and the live parity probe. None when the daemon ran
    without the mesh dispatch (the record section is then omitted —
    tests/test_bench_record.py requires it from r09 on)."""
    keys = {"solverd_mesh_devices",
            "solverd_mesh_pods_axis",
            "solverd_mesh_node_shards",
            "solverd_mesh_waves_total",
            "solverd_mesh_transfer_bytes_total",
            "solverd_mesh_reshard_bytes_total",
            "solverd_mesh_resident_bytes",
            "solverd_mesh_shard_bytes_per_device",
            "solverd_mesh_parity_checks_total",
            "solverd_mesh_parity_divergent_total"}
    vals = {}
    for line in raw.splitlines():
        key, _, val = line.rpartition(" ")
        if key in keys:
            vals[key] = float(val)
    if vals.get("solverd_mesh_devices", 0.0) <= 0:
        return None
    out = {
        "devices": int(vals["solverd_mesh_devices"]),
        "pods_axis": int(vals.get("solverd_mesh_pods_axis", 1)),
        "node_shards": int(vals.get("solverd_mesh_node_shards", 0)),
        "waves": int(vals.get("solverd_mesh_waves_total", 0)),
        "transfer_bytes": int(
            vals.get("solverd_mesh_transfer_bytes_total", 0)),
        "reshard_bytes": int(
            vals.get("solverd_mesh_reshard_bytes_total", 0)),
        "resident_bytes": int(vals.get("solverd_mesh_resident_bytes", 0)),
        "shard_bytes_per_device": int(
            vals.get("solverd_mesh_shard_bytes_per_device", 0)),
        "parity_checks": int(
            vals.get("solverd_mesh_parity_checks_total", 0)),
        "parity_divergent": int(
            vals.get("solverd_mesh_parity_divergent_total", 0)),
    }
    m_sum, m_count, m_buckets = _parse_hist(raw, "solverd_mesh_solve_seconds")
    out["solve_waves"] = int(m_count)
    out["solve_p50_ms"] = round(
        _hist_quantile(m_buckets, m_count, 0.5) * 1000, 2) if m_count else 0.0
    out["solve_p95_ms"] = round(
        _hist_quantile(m_buckets, m_count, 0.95) * 1000, 2) if m_count else 0.0
    s_sum, s_count, s_buckets = _parse_hist(
        raw, "solverd_mesh_single_device_seconds")
    out["single_device_probes"] = int(s_count)
    out["single_device_p50_ms"] = round(
        _hist_quantile(s_buckets, s_count, 0.5) * 1000, 2) if s_count else 0.0
    sub = _scrape_solverd_submesh(raw)
    if sub is not None:
        out["submesh"] = sub
    return out


def _scrape_solverd_submesh(raw: str):
    """The solverd_submesh_* family (models/submesh.py via MeshExecutor):
    kube-horizon's active sub-mesh solve — how many waves ran on a
    compacted node axis, the kept fraction (the compression the keep
    rule actually bought), host-side planning cost, and the live
    compacted-vs-full bit-identity probe. None only when the daemon
    predates the family; a mesh run that never engaged still discloses
    waves 0 / full_waves N (required from r17 on)."""
    keys = {"solverd_submesh_waves_total",
            "solverd_submesh_full_waves_total",
            "solverd_submesh_nodes_kept_total",
            "solverd_submesh_nodes_total",
            "solverd_submesh_parity_checks_total",
            "solverd_submesh_parity_divergent_total"}
    vals = {}
    for line in raw.splitlines():
        key, _, val = line.rpartition(" ")
        if key in keys:
            vals[key] = float(val)
    if "solverd_submesh_waves_total" not in vals:
        return None
    kept = int(vals.get("solverd_submesh_nodes_kept_total", 0))
    total = int(vals.get("solverd_submesh_nodes_total", 0))
    out = {
        "waves": int(vals["solverd_submesh_waves_total"]),
        "full_waves": int(vals.get("solverd_submesh_full_waves_total", 0)),
        "nodes_kept": kept,
        "nodes_total": total,
        "kept_fraction": round(kept / total, 3) if total else 0.0,
        "parity_checks": int(
            vals.get("solverd_submesh_parity_checks_total", 0)),
        "parity_divergent": int(
            vals.get("solverd_submesh_parity_divergent_total", 0)),
    }
    c_sum, c_count, c_buckets = _parse_hist(
        raw, "solverd_submesh_compact_seconds")
    out["compact_p50_ms"] = round(
        _hist_quantile(c_buckets, c_count, 0.5) * 1000, 2) if c_count else 0.0
    return out


def _parse_hist(raw: str, base: str):
    """-> (sum, count, sorted [(le, cumcount)]) for one histogram family."""
    buckets, total, count = [], 0.0, 0.0
    for line in raw.splitlines():
        if line.startswith(base + "_bucket"):
            le = line.split('le="', 1)[1].split('"', 1)[0]
            buckets.append((float("inf") if le == "+Inf" else float(le),
                            float(line.rsplit(None, 1)[1])))
        elif line.startswith(base + "_sum"):
            total = float(line.rsplit(None, 1)[1])
        elif line.startswith(base + "_count"):
            count = float(line.rsplit(None, 1)[1])
    return total, count, sorted(buckets)


def _hist_quantile(buckets, count: float, q: float) -> float:
    target = q * count
    prev_le, prev_n = 0.0, 0.0
    for le, n in buckets:
        if n >= target:
            if le == float("inf"):
                # the rank fell beyond the largest bounded bucket: report
                # the overflow loudly (Histogram.quantile semantics —
                # widen the envelope rather than trusting a capped
                # in-envelope-looking number)
                return float("inf")
            span = n - prev_n
            frac = (target - prev_n) / span if span else 1.0
            return prev_le + (le - prev_le) * frac
        prev_le, prev_n = le, n
    return prev_le


def _merge_hist(raws, base: str):
    """_parse_hist merged across worker scrapes: sums and counts add,
    and the cumulative bucket counts add le-wise (every worker ships
    identical bucket bounds)."""
    total = count = 0.0
    bmap: dict = {}
    for raw in raws:
        s, c, buckets = _parse_hist(raw, base)
        total += s
        count += c
        for le, n in buckets:
            bmap[le] = bmap.get(le, 0.0) + n
    return total, count, sorted(bmap.items())


def _scrape_apiserver(master: str) -> dict:
    """The apiserver_* hot-path evidence from the server's /metrics:
    frame-cache effectiveness, fan-out write batching, lag drops, and the
    batch-bind size/latency envelope (docs/design/apiserver-hotpath.md)."""
    raw = urllib.request.urlopen(f"{master}/metrics", timeout=5
                                 ).read().decode()
    return _parse_apiserver([raw])


def _parse_apiserver(raws) -> dict:
    """One record ``apiserver`` section from one or more /metrics
    scrapes — with an SO_REUSEPORT fleet, one raw text per WORKER, so
    counters sum and histograms merge into fleet-wide quantiles."""
    keys = ("apiserver_watch_frame_cache_hits_total",
            "apiserver_watch_frame_cache_misses_total",
            "apiserver_watch_frame_seeds_total",
            "apiserver_watch_lag_drops_total",
            "watch_events_coalesced_total",
            "watch_events_dropped_total",
            "watch_lag_resyncs_total")
    vals = {k: 0.0 for k in keys}
    for raw in raws:
        for line in raw.splitlines():
            for key in keys:
                if line.startswith(key + " "):
                    vals[key] += float(line.rsplit(None, 1)[1])
    hits = vals["apiserver_watch_frame_cache_hits_total"]
    misses = vals["apiserver_watch_frame_cache_misses_total"]
    out = {
        "frame_cache_hits": int(hits),
        "frame_cache_misses": int(misses),
        "frame_cache_hit_rate": round(hits / (hits + misses), 3)
        if hits + misses else 0.0,
        "frame_seeds": int(
            vals["apiserver_watch_frame_seeds_total"]),
        "watch_lag_drops": int(
            vals["apiserver_watch_lag_drops_total"]),
        "watch_events_coalesced": int(
            vals["watch_events_coalesced_total"]),
        "watch_events_dropped": int(
            vals["watch_events_dropped_total"]),
    }
    fo_sum, fo_count, _ = _merge_hist(raws, "apiserver_watch_fanout_seconds")
    wf_sum, wf_count, _ = _merge_hist(raws, "apiserver_watch_write_frames")
    out["fanout_seconds"] = round(fo_sum, 2)
    out["fanout_writes"] = int(fo_count)
    if wf_count:
        out["frames_per_write"] = round(wf_sum / wf_count, 2)
    sz_sum, sz_count, _ = _merge_hist(raws, "apiserver_batch_bind_size")
    s_sum, s_count, s_buckets = _merge_hist(raws,
                                            "apiserver_batch_bind_seconds")
    out["batch_bind_requests"] = int(sz_count)
    out["batch_bind_bindings"] = int(sz_sum)
    out["batch_bind_p50_ms"] = round(
        _hist_quantile(s_buckets, s_count, 0.5) * 1000, 2) if s_count else 0.0
    out["batch_bind_p95_ms"] = round(
        _hist_quantile(s_buckets, s_count, 0.95) * 1000, 2) if s_count else 0.0
    out["bind_server_ms_per_pod"] = round(s_sum / sz_sum * 1000, 3) \
        if sz_sum else 0.0
    return out


def _scrape_worker_raws(master: str, n_api: int) -> dict:
    """{worker_index: /metrics text} for an SO_REUSEPORT fleet: each
    GET lands on an arbitrary worker (keyed by the
    ``apiserver_worker_index`` identity gauge), so the shared port is
    hit until all N have answered or the attempt budget runs out — a
    missed worker is DISCLOSED by the caller, never silently absent.
    Re-scrapes of a seen worker keep the newest text."""
    raws: dict = {}
    for _ in range(max(8, 24 * n_api)):
        if len(raws) >= n_api:
            break
        try:
            raw = urllib.request.urlopen(f"{master}/metrics", timeout=5
                                         ).read().decode()
        except Exception:
            continue
        for line in raw.splitlines():
            if line.startswith("apiserver_worker_index "):
                idx = int(float(line.rsplit(None, 1)[1]))
                if idx >= 0:
                    raws[idx] = raw
                break
    return raws


def _worker_disclosure(raws: dict, feed_s: float, pid_by_name: dict) -> list:
    """Per-worker record rows (required at --apiservers > 1): request
    share, frame-cache effectiveness, cross-process seed traffic, and
    CPU seconds per worker."""
    rows = []
    for idx in sorted(raws):
        raw = raws[idx]
        requests = 0.0
        singles = {"apiserver_worker_pid": 0.0,
                   "apiserver_watch_frame_cache_hits_total": 0.0,
                   "apiserver_watch_frame_cache_misses_total": 0.0,
                   "apiserver_cache_seed_published_total": 0.0,
                   "apiserver_cache_seed_imported_total": 0.0,
                   "apiserver_cache_seed_hits_total": 0.0,
                   "apiserver_cache_seed_ring_drops_total": 0.0}
        for line in raw.splitlines():
            if line.startswith("apiserver_request_count{"):
                requests += float(line.rsplit(None, 1)[1])
                continue
            for key in singles:
                if line.startswith(key + " "):
                    singles[key] = float(line.rsplit(None, 1)[1])
        pid = int(singles["apiserver_worker_pid"])
        hits = singles["apiserver_watch_frame_cache_hits_total"]
        misses = singles["apiserver_watch_frame_cache_misses_total"]
        rows.append({
            "worker": idx,
            "pid": pid,
            "requests": int(requests),
            "request_rate_per_s": round(requests / feed_s, 1)
            if feed_s else 0.0,
            "frame_cache_hit_rate": round(hits / (hits + misses), 3)
            if hits + misses else 0.0,
            "cache_seed_published": int(
                singles["apiserver_cache_seed_published_total"]),
            "cache_seed_imported": int(
                singles["apiserver_cache_seed_imported_total"]),
            "cache_seed_hits": int(
                singles["apiserver_cache_seed_hits_total"]),
            "cache_seed_ring_drops": int(
                singles["apiserver_cache_seed_ring_drops_total"]),
            "cpu_s": _proc_cpu_s(pid_by_name.get(f"apiserver{idx}", pid)),
        })
    return rows


def _label_of(line: str, key: str) -> str:
    return line.split(key + '="', 1)[1].split('"', 1)[0]


def _scrape_fairshed(master: str) -> dict:
    """kube-fairshed admission evidence from the apiserver's /metrics:
    per-flow admitted/shed counts (by reason), the MUST-BE-ZERO
    system-flow shed invariant counter, the workload backlog depth, and
    per-flow queue-wait p95 — the record's ``fairshed`` section
    (required whenever the record carries the ``overload`` marker)."""
    raw = urllib.request.urlopen(f"{master}/metrics", timeout=5
                                 ).read().decode()
    flows: dict = {}
    system_shed = backlog = 0
    qw: dict = {}   # flow -> {le: cumcount}
    for line in raw.splitlines():
        if not line or line.startswith("#"):
            continue
        val = line.rsplit(None, 1)[-1]
        if line.startswith("request_admitted_total{"):
            flow = _label_of(line, "flow")
            d = flows.setdefault(flow, {"admitted": 0, "shed": {}})
            d["admitted"] += int(float(val))
        elif line.startswith("request_shed_total{"):
            flow = _label_of(line, "flow")
            reason = _label_of(line, "reason")
            d = flows.setdefault(flow, {"admitted": 0, "shed": {}})
            d["shed"][reason] = d["shed"].get(reason, 0) + int(float(val))
        elif line.startswith("fairshed_system_shed_total "):
            system_shed = int(float(val))
        elif line.startswith("fairshed_backlog_depth "):
            backlog = int(float(val))
        elif line.startswith("request_queue_wait_seconds_bucket{"):
            flow = _label_of(line, "flow")
            le_s = _label_of(line, "le")
            le = float("inf") if le_s == "+Inf" else float(le_s)
            qw.setdefault(flow, {})[le] = float(val)
    p95 = {}
    for flow, bmap in qw.items():
        buckets = sorted(bmap.items())
        count = max(bmap.values()) if bmap else 0.0
        p95[flow] = round(_hist_quantile(buckets, count, 0.95), 4) \
            if count else None
    return {
        "flows": flows,
        "admitted_total": sum(d["admitted"] for d in flows.values()),
        "shed_total": sum(sum(d["shed"].values())
                          for d in flows.values()),
        "system_shed": system_shed,
        "backlog_depth": backlog,
        "queue_wait_p95_s": p95,
    }


def bind_parity_probe(client, api, n_nodes: int, k: int = 64) -> dict:
    """Zero-divergence evidence for the batch endpoint ON THE LIVE SERVER:
    two identical pod sets, one bound per-pod (POST pods/{name}/binding),
    one via bindings:batch, with an intentional double-bind in each arm.
    Runs before the scheduler starts so nothing races the probe. Returns
    {checked, divergent, conflict_parity}."""
    ns = "parity"
    plan = [(f"parity-{arm}-{i:03d}", f"node-{i % n_nodes:05d}")
            for arm in ("a", "b") for i in range(k)]
    for name, _host in plan:
        client.pods(ns).create(api.Pod(
            metadata=api.ObjectMeta(name=name, namespace=ns),
            spec=api.PodSpec(containers=[api.Container(
                name="c", image="img")])))

    def binding(name, host):
        return api.Binding(metadata=api.ObjectMeta(name=name, namespace=ns),
                           pod_name=name, host=host)

    a_codes = []
    for name, host in plan[:k] + [plan[0]]:       # last item re-binds: 409
        try:
            client.pods(ns).bind(binding(name, host))
            a_codes.append(0)
        except Exception as e:
            a_codes.append(getattr(e, "code", -1))
    res = client.pods(ns).bind_many(api.BindingList(
        items=[binding(n, h) for n, h in plan[k:] + [plan[k]]]))
    b_codes = [r.code for r in res.items]
    divergent = sum(1 for ca, cb in zip(a_codes, b_codes) if ca != cb)
    hosts = {p.metadata.name: p.spec.host
             for p in client.pods(ns).list().items}
    for i, (name, want) in enumerate(plan):
        peer = plan[(i + k) % (2 * k)][0]
        if hosts.get(name) != want or hosts.get(name) != hosts.get(peer):
            divergent += 1
    return {"checked": len(plan) + 2, "divergent": divergent,
            "conflict_parity": a_codes[-1] == b_codes[-1] == 409}


def bind_cost_probe(client, api, n_nodes: int, k: int = 512,
                    rounds: int = 2, per_pod_n: int = 256) -> dict:
    """Isolated apiserver bind cost on the QUIET server — the number
    comparable to r07's commit-derived ~1.8 ms/bind, which r07 measured
    on mostly post-feed (quiet) waves. Two arms: K-binding batch
    requests (the bindings:batch path the scheduler uses) and a per-pod
    control arm (one POST pods/{name}/binding per pod). Client-observed
    wall per bind, so it includes client encode/decode + the wire —
    conservative for the server."""
    import time as _time
    ns = "probe"
    total = k * rounds + per_pod_n
    names = [f"probe-{i:05d}" for i in range(total)]

    def create(lo, hi):
        for i in range(lo, hi):
            client.pods(ns).create(api.Pod(
                metadata=api.ObjectMeta(name=names[i], namespace=ns),
                spec=api.PodSpec(containers=[api.Container(
                    name="c", image="img")])))

    def binding(i):
        return api.Binding(
            metadata=api.ObjectMeta(name=names[i], namespace=ns),
            pod_name=names[i], host=f"node-{i % n_nodes:05d}")

    # create-then-bind PER ROUND (only the binds are timed): the
    # probe's created-but-unbound footprint stays <= max(k, per_pod_n),
    # so it never trips the kube-fairshed backlog governor the way a
    # create-everything-first pass would (and never leaves dangling
    # pending pods behind if it aborts mid-way)
    batch_s = 0.0
    for r in range(rounds):
        create(r * k, (r + 1) * k)
        t0 = _time.perf_counter()
        res = client.pods(ns).bind_many(api.BindingList(
            items=[binding(i) for i in range(r * k, (r + 1) * k)]))
        batch_s += _time.perf_counter() - t0
        assert not any(x.error for x in res.items)
    batch_ms = batch_s / (k * rounds) * 1000
    create(k * rounds, total)
    t0 = _time.perf_counter()
    for i in range(k * rounds, total):
        client.pods(ns).bind(binding(i))
    per_pod_ms = (_time.perf_counter() - t0) / per_pod_n * 1000
    return {"batch_ms_per_pod": round(batch_ms, 3),
            "per_pod_ms": round(per_pod_ms, 3),
            "pods": total}


def _proc_cpu_s(pid: int) -> float:
    """utime+stime of one process from /proc (Linux), in seconds."""
    with open(f"/proc/{pid}/stat") as fh:
        parts = fh.read().rsplit(") ", 1)[1].split()
    return (int(parts[11]) + int(parts[12])) / os.sysconf("SC_CLK_TCK")


# The committed-record contract (tests/test_bench_record.py): a CHURN_MP
# record must carry these so future rounds can't silently drop the
# delta-wire evidence or the per-stage CPU budget the acceptance gates
# read. solverd keys are required only when the run had a daemon;
# apiserver hot-path keys are required from r08 on.
RECORD_FIELDS = ("config", "topology", "offered_pods_per_s",
                 "sustained_pods_per_s", "all_bound", "feed_s", "total_s",
                 "scheduler_waves", "cpu_budget_s", "host_cores")
SOLVERD_DELTA_FIELDS = ("delta_hits", "delta_full_frames", "delta_resyncs",
                        "delta_hit_rate", "delta_bytes_shipped",
                        "delta_bytes_saved")
APISERVER_FIELDS = ("frame_cache_hits", "frame_cache_misses",
                    "frame_cache_hit_rate", "watch_lag_drops",
                    "batch_bind_requests", "batch_bind_bindings",
                    "batch_bind_p50_ms", "bind_server_ms_per_pod",
                    "per_bind_ms_live", "bind_parity", "bind_probe")
# The mesh-sharded production solve evidence (solver/mesh_exec.py),
# required under solverd from r09 on: mesh topology, the mesh-vs-single
# solve quantiles, resident-plane traffic, and the live parity probe.
SOLVERD_MESH_FIELDS = ("devices", "pods_axis", "node_shards", "waves",
                       "transfer_bytes", "reshard_bytes",
                       "shard_bytes_per_device", "solve_p50_ms",
                       "single_device_p50_ms", "parity_checks",
                       "parity_divergent")
# Pod-lifecycle latency evidence (kube-trace + PodLatencyMetrics),
# required from r10 on: per-pod e2e quantiles, the bind->watch-observe
# leg, and the trace-collection health counters (shard count, spans
# dropped) so a record claiming "overhead proven" also proves the
# instrument itself wasn't silently lossy.
LATENCY_FIELDS = ("e2e_count", "e2e_p50_s", "e2e_p95_s", "e2e_p99_s",
                  "watch_observe_count", "watch_observe_p50_s",
                  "trace_shards", "spans_dropped")
# kube-flightrec evidence, required from r11 on: the continuous
# control-plane time-series (the curves every wall to date had to be
# reconstructed without) and the SLO alarm transition log. A clean
# contract run carries alarms: [] — proven quiet, not assumed. The
# downsampled headline series ride the record; the full-resolution
# merged series live in the <out>_timeline.json sidecar.
TIMELINE_FIELDS = ("sample_period_s", "series", "headline")
TIMELINE_MIN_SERIES = 5
# kube-preempt evidence, required whenever a record claims the
# priority-storm shape: evict+bind counts, the MUST-BE-ZERO invariant
# counter, and the preempt-to-bind latency section.
PREEMPTION_FIELDS = ("attempts", "victims", "conflicts",
                     "higher_evictions", "bind_count", "bind_p50_s",
                     "bind_p95_s")
# kube-chaos evidence, required whenever a record claims a fault-
# injected run (a ``chaos`` section present): the declarative kill
# schedule, what actually got killed (events), per-component restart
# counts and respawn-to-ready recovery times — plus the ``store``
# section proving the WAL path (group commits, compactions, byte sizes)
# and what the LAST recovery of the (possibly respawned) kube-store
# cost. A chaos claim without these is an anecdote.
CHAOS_FIELDS = ("schedule", "events", "restarts", "recovery_s")
STORE_FIELDS = ("wal_records", "wal_ops", "wal_group_commits",
                "wal_bytes_written", "wal_size", "snapshot_size",
                "compactions", "torn", "recovery")
# kube-explain evidence, required from r13 on: why-pending visibility.
# A clean contract run discloses pods: 0 with an empty reason histogram
# — proving the layer costs nothing when every pod binds — and the
# async-event-recorder posted/dropped counters ride along so an event
# storm can never shed diagnostics silently.
UNSCHEDULABLE_FIELDS = ("pods", "reasons", "explain_invocations",
                        "explain_seconds", "explain_skipped",
                        "events_posted", "events_dropped")
# kube-fairshed evidence, required whenever a record claims an overload
# run (an ``overload`` marker present): per-flow admitted/shed counts,
# the system-flow shed invariant (MUST read 0 — the starvation-freedom
# contract), the backlog governor's depth, queue-wait quantiles, and
# the feeders' Retry-After-driven retry count. An overload claim whose
# lower bands shed nothing proves the governor never engaged.
FAIRSHED_FIELDS = ("flows", "admitted_total", "shed_total", "system_shed",
                   "backlog_depth", "queue_wait_p95_s", "retried_429")
# kube-defrag evidence, required whenever a record claims a
# fragment-storm run (a ``fragmentation`` section present): the
# harness-measured score before/after the defrag window, migrations
# committed vs lost to commit guards (409/404), nodes drained
# (cordoned) vs emptied (voluntary consolidation), the cordon-drain
# contract (every cordoned node fully emptied), the no-half-moves
# proof (zero unbound pods after the window — an evict without its
# bind would strand one), and the MUST-BE-ZERO score-regression
# invariant counter.
FRAGMENTATION_FIELDS = ("score_before", "score_after", "waves",
                        "migrations_committed", "migrations_409",
                        "nodes_drained", "nodes_emptied", "cordoned",
                        "cordoned_drained_ok", "unbound_after",
                        "score_regressions")
# kube-horizon per-worker disclosure, required from r17 on whenever the
# record claims an SO_REUSEPORT fleet (apiserver.workers_configured
# > 1): one row per worker — request share, frame-cache effectiveness,
# cross-process seed traffic (published / imported / cache hits /
# ring laps), and CPU seconds — so "N workers scaled" is per-worker
# evidence, not an aggregate assertion that one hot worker could fake.
APISERVER_WORKER_FIELDS = ("worker", "pid", "requests",
                           "request_rate_per_s", "frame_cache_hit_rate",
                           "cache_seed_published", "cache_seed_imported",
                           "cache_seed_hits", "cache_seed_ring_drops",
                           "cpu_s")
# kube-horizon active sub-mesh evidence, required under solverd.mesh
# from r17 on: compacted-vs-full wave split, the kept fraction the keep
# rule bought, host planning cost, and the compacted-vs-full bit-
# identity probe (parity_divergent MUST read 0 — the compaction is
# decision-preserving by construction and the probe keeps that claim
# live, docs/design/batch-solver.md §active-sub-mesh).
SOLVERD_SUBMESH_FIELDS = ("waves", "full_waves", "nodes_kept",
                          "nodes_total", "kept_fraction", "compact_p50_ms",
                          "parity_checks", "parity_divergent")

# kube-slipstream (r19): encoder resync discipline + prewarm evidence.
SLIPSTREAM_FIELDS = ("prewarm_enabled", "prewarm_compile_s",
                     "prewarm_compiles", "resync_replay",
                     "resync_replay_in_window", "resync_full",
                     "resync_full_in_window", "resync_full_reasons",
                     "stall_max_s")


def validate_record(rec: dict, round_no: int = 8) -> list:
    """-> list of missing/malformed field paths (empty = conformant).
    ``round_no`` gates fields introduced mid-series (apiserver hot-path
    evidence exists from r08 on). Error records (a run that aborted) are
    exempt beyond their marker."""
    if "error" in rec:
        return []
    missing = [k for k in RECORD_FIELDS if k not in rec]
    sd = rec.get("solverd")
    if isinstance(sd, dict) and "error" not in sd:
        missing += [f"solverd.{k}" for k in SOLVERD_DELTA_FIELDS
                    if k not in sd]
        if round_no >= 9:
            # r09 claimed the mesh-sharded solve; every later record must
            # carry the mesh section so the solve-stage evidence (device
            # count, mesh-vs-single p50, reshard bytes, parity) can't be
            # silently dropped
            mesh = sd.get("mesh")
            if not isinstance(mesh, dict):
                missing.append("solverd.mesh")
            elif "error" not in mesh:
                missing += [f"solverd.mesh.{k}" for k in SOLVERD_MESH_FIELDS
                            if k not in mesh]
    if round_no >= 8:
        ap = rec.get("apiserver")
        if not isinstance(ap, dict):
            missing.append("apiserver")
        elif "error" not in ap:
            missing += [f"apiserver.{k}" for k in APISERVER_FIELDS
                        if k not in ap]
    if round_no >= 10:
        # r10 introduced the pod-lifecycle latency section (kube-trace);
        # every later record must carry it so the e2e view can't be
        # silently dropped (earlier records grandfathered by this gate)
        lat = rec.get("latency")
        if not isinstance(lat, dict):
            missing.append("latency")
        elif "error" not in lat:
            missing += [f"latency.{k}" for k in LATENCY_FIELDS
                        if k not in lat]
    if round_no >= 11:
        # r11 introduced kube-flightrec: the timeline section (>= 5
        # headline series spanning the run) and the SLO alarm transition
        # log are part of the record contract from here on
        tl = rec.get("timeline")
        if not isinstance(tl, dict):
            missing.append("timeline")
        elif "error" not in tl:
            missing += [f"timeline.{k}" for k in TIMELINE_FIELDS
                        if k not in tl]
            series = tl.get("series")
            if isinstance(series, dict) and \
                    len(series) < TIMELINE_MIN_SERIES:
                missing.append(
                    f"timeline.series:{len(series)}<{TIMELINE_MIN_SERIES}")
        if not isinstance(rec.get("alarms"), list):
            missing.append("alarms")
    if round_no >= 17:
        # r17 introduced kube-horizon: the apiserver section must say
        # how many workers were configured, and a multi-worker fleet
        # must disclose every worker's row (a missed scrape shard is a
        # conformance failure, not a silent absence)
        ap = rec.get("apiserver")
        if isinstance(ap, dict) and "error" not in ap:
            if "workers_configured" not in ap:
                missing.append("apiserver.workers_configured")
            elif ap["workers_configured"] > 1:
                workers = ap.get("workers")
                if not isinstance(workers, list):
                    missing.append("apiserver.workers")
                else:
                    if len(workers) < ap["workers_configured"]:
                        missing.append(
                            f"apiserver.workers:{len(workers)}"
                            f"<{ap['workers_configured']}")
                    for i, w in enumerate(workers):
                        missing += [f"apiserver.workers[{i}].{k}"
                                    for k in APISERVER_WORKER_FIELDS
                                    if k not in w]
        # r17 also introduced the active sub-mesh solve: the mesh
        # section must disclose the compaction split and the live
        # parity evidence, and a divergent probe is a contract
        # violation, not a statistic
        mesh = (sd or {}).get("mesh") if isinstance(sd, dict) else None
        if isinstance(mesh, dict) and "error" not in mesh:
            subm = mesh.get("submesh")
            if not isinstance(subm, dict):
                missing.append("solverd.mesh.submesh")
            else:
                missing += [f"solverd.mesh.submesh.{k}"
                            for k in SOLVERD_SUBMESH_FIELDS
                            if k not in subm]
                if subm.get("parity_divergent", 0) != 0:
                    missing.append(
                        "solverd.mesh.submesh.parity_divergent:nonzero")
    if round_no >= 18:
        # r18 introduced the kube-stripe feeder push: the record must
        # disclose the load generator's own normalized cost — the
        # number the coalesced-sendall/batched-ack claim is judged on
        if "feeder_cpu_s_per_10k" not in rec:
            missing.append("feeder_cpu_s_per_10k")
    if round_no >= 19:
        # r19 is kube-slipstream: the record must carry the slipstream
        # section, and the headline invariant — zero FULL encoder
        # re-encodes inside the load window (journal replay covered
        # every resync) — is a conformance requirement, not a statistic
        slip = rec.get("slipstream")
        if not isinstance(slip, dict):
            missing.append("slipstream")
        elif "error" not in slip:
            missing += [f"slipstream.{k}" for k in SLIPSTREAM_FIELDS
                        if k not in slip]
            if slip.get("resync_full_in_window", 0) != 0:
                missing.append("slipstream.resync_full_in_window:nonzero")
    if round_no >= 13:
        # r13 introduced kube-explain: the unschedulable section (reason
        # histogram + explain cost + event-recorder loss disclosure) is
        # part of the record contract from here on
        un = rec.get("unschedulable")
        if not isinstance(un, dict):
            missing.append("unschedulable")
        elif "error" not in un:
            missing += [f"unschedulable.{k}" for k in UNSCHEDULABLE_FIELDS
                        if k not in un]
    if rec.get("priority_storm"):
        pr = rec.get("preemption")
        if not isinstance(pr, dict):
            missing.append("preemption")
        elif "error" not in pr:
            missing += [f"preemption.{k}" for k in PREEMPTION_FIELDS
                        if k not in pr]
    if rec.get("overload") is not None:
        fsec = rec.get("fairshed")
        if not isinstance(fsec, dict):
            missing.append("fairshed")
        elif "error" not in fsec:
            missing += [f"fairshed.{k}" for k in FAIRSHED_FIELDS
                        if k not in fsec]
            if fsec.get("system_shed", 0) != 0:
                # the starvation-freedom invariant is part of the record
                # CONTRACT: an overload record with system sheds is
                # non-conformant, not merely unflattering
                missing.append("fairshed.system_shed:nonzero")
    if rec.get("fragmentation") is not None:
        fr = rec["fragmentation"]
        if not isinstance(fr, dict):
            missing.append("fragmentation")
        elif "error" not in fr:
            missing += [f"fragmentation.{k}" for k in FRAGMENTATION_FIELDS
                        if k not in fr]
            # the invariants are part of the record CONTRACT: a
            # fragment-storm record whose score regressed, whose
            # cordoned set did not drain, or which left a pod evicted
            # but unbound is non-conformant, not merely unflattering
            if fr.get("score_regressions", 0) != 0:
                missing.append("fragmentation.score_regressions:nonzero")
            if "cordoned_drained_ok" in fr and \
                    not fr["cordoned_drained_ok"]:
                missing.append("fragmentation.cordoned_drained_ok:false")
            if fr.get("unbound_after", 0) != 0:
                missing.append("fragmentation.unbound_after:nonzero")
            if "score_before" in fr and "score_after" in fr and \
                    fr["score_after"] >= fr["score_before"]:
                missing.append("fragmentation.score:not-improved")
    if rec.get("chaos") is not None:
        ch = rec["chaos"]
        if not isinstance(ch, dict):
            missing.append("chaos")
        else:
            missing += [f"chaos.{k}" for k in CHAOS_FIELDS if k not in ch]
        st = rec.get("store")
        if not isinstance(st, dict):
            missing.append("store")
        elif "error" not in st:
            missing += [f"store.{k}" for k in STORE_FIELDS if k not in st]
    cb = rec.get("cpu_budget_s")
    if cb is not None and not isinstance(cb, dict):
        missing.append("cpu_budget_s:not-a-dict")
    return missing


# -- kube-chaos: declarative kill schedule ----------------------------------

_CHAOS_ALIASES = {"store": "storeserver", "kube-store": "storeserver",
                  "apiserver": "apiserver0", "scheduler": "scheduler0"}


def parse_chaos(spec: str) -> list:
    """``'apiserver@120s,solverd@240s:SIGKILL,scheduler@300s'`` ->
    ``[{"component", "t_s", "signal"}, ...]`` sorted by time.

    Components name the harness's children: ``apiserverN`` /
    ``schedulerN`` (bare ``apiserver``/``scheduler`` = worker 0),
    ``solverd``, ``storeserver`` (aliases ``store``, ``kube-store``).
    Times are seconds after the offered-load window opens (feeders
    launch). The default signal is SIGKILL — the chaos contract is
    crash recovery, not graceful shutdown.

    Latency injection (kube-fairshed: overload and gray slowness
    compose in ONE schedule): ``apiserver@120s:delay=250ms`` pauses
    the live process for exactly that long (SIGSTOP -> sleep ->
    SIGCONT) instead of killing it — entries carry ``delay_s`` in
    place of ``signal``. Durations take us/ms/s/m suffixes
    (util/chaos.parse_duration; the in-process twin is the
    ``apiserver.dispatch`` delay seam)."""
    import signal as signal_mod

    from kubernetes_tpu.util.chaos import parse_duration
    out = []
    for part in filter(None, (p.strip() for p in spec.split(","))):
        if "@" not in part:
            raise ValueError(f"chaos entry {part!r}: expected "
                             "component@TIME[s][:SIGNAL|:delay=DUR]")
        name, _, rest = part.partition("@")
        t_str, _, sig = rest.partition(":")
        t_str = t_str.strip().rstrip("s")
        try:
            t_s = float(t_str)
        except ValueError:
            raise ValueError(
                f"chaos entry {part!r}: bad time {t_str!r}") from None
        name = _CHAOS_ALIASES.get(name.strip(), name.strip())
        sig = (sig or "SIGKILL").strip()
        if sig.lower().startswith("delay="):
            try:
                delay_s = parse_duration(sig.partition("=")[2])
            except ValueError:
                raise ValueError(f"chaos entry {part!r}: bad delay "
                                 f"duration {sig!r}") from None
            out.append({"component": name, "t_s": t_s,
                        "delay_s": delay_s})
            continue
        sig = sig.upper()
        if not sig.startswith("SIG"):
            sig = "SIG" + sig
        if not hasattr(signal_mod, sig):
            raise ValueError(f"chaos entry {part!r}: unknown signal {sig}")
        out.append({"component": name, "t_s": t_s, "signal": sig})
    return sorted(out, key=lambda e: e["t_s"])


def _scrape_store(metrics_port: int) -> dict:
    """The WAL-path evidence from kube-store's --metrics-port: the
    ``store_wal_*`` counters (reset by a respawn — the scraped values
    cover the CURRENT process's life, which for a chaos run is exactly
    the post-kill story) plus the /healthz recovery disclosure (what the
    last crash recovery replayed and how long it took)."""
    raw = urllib.request.urlopen(
        f"http://127.0.0.1:{metrics_port}/metrics", timeout=5
    ).read().decode()
    vals = {}
    keys = {"store_wal_records_total", "store_wal_ops_total",
            "store_wal_group_commits_total", "store_wal_fsyncs_total",
            "store_wal_bytes_total", "store_wal_compactions_total",
            "store_wal_size_bytes", "store_snapshot_size_bytes",
            "store_wal_torn_bytes_total"}
    for line in raw.splitlines():
        key, _, val = line.rpartition(" ")
        if key in keys:
            vals[key] = float(val)
    out = {
        "wal_records": int(vals.get("store_wal_records_total", 0)),
        "wal_ops": int(vals.get("store_wal_ops_total", 0)),
        "wal_group_commits": int(
            vals.get("store_wal_group_commits_total", 0)),
        "wal_fsyncs": int(vals.get("store_wal_fsyncs_total", 0)),
        "wal_bytes_written": int(vals.get("store_wal_bytes_total", 0)),
        "compactions": int(vals.get("store_wal_compactions_total", 0)),
        # record keys carry no _bytes suffix (units documented in
        # docs/design/ha.md): the metrics-sync vet rule reserves
        # series-shaped names for real registry series
        "wal_size": int(vals.get("store_wal_size_bytes", 0)),
        "snapshot_size": int(vals.get("store_snapshot_size_bytes", 0)),
        "torn": int(vals.get("store_wal_torn_bytes_total", 0)),
    }
    health = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{metrics_port}/healthz", timeout=5).read())
    out["recovery"] = health.get("recovery", {})
    return out


def _scrape_pod_latency(ports) -> dict:
    """Pod-lifecycle latency quantiles (util/metrics.PodLatencyMetrics)
    merged across every scheduler worker's /metrics: create ->
    bind-committed (e2e) and bind -> watcher-observed. The histograms
    are always on; this is the causal per-pod view of where the 1000/s
    contract's latency goes, scraped into the record's ``latency``
    section (required for r10+ records)."""
    merged = {}
    for port in ports:
        raw = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
        for base, key in (("pod_e2e_scheduling_seconds", "e2e"),
                          ("pod_watch_observe_seconds", "watch_observe")):
            total, count, buckets = _parse_hist(raw, base)
            m = merged.setdefault(key, [0.0, 0.0, {}])
            m[0] += total
            m[1] += count
            for le, n in buckets:
                m[2][le] = m[2].get(le, 0.0) + n
    out = {}
    for key, (total, count, bmap) in merged.items():
        buckets = sorted(bmap.items())
        out[f"{key}_count"] = int(count)
        # Histogram.quantile semantics (util/metrics.py): an empty
        # histogram has NO quantiles — emit null, never a fake 0.0, so
        # a dead instrument fails loudly in the record instead of
        # conforming with plausible-looking zeros
        out[f"{key}_mean_s"] = round(total / count, 4) if count else None
        for q, name in ((0.5, "p50"), (0.95, "p95"), (0.99, "p99")):
            out[f"{key}_{name}_s"] = round(
                _hist_quantile(buckets, count, q), 4) if count else None
    return out


def _collect_trace_shards(master: str, ports, n_api: int = 1):
    """Drain every process's GET /debug/trace span ring -> one shard
    per pid. With N apiserver workers sharing the listen port via
    SO_REUSEPORT, each GET lands on an arbitrary worker — draining is
    destructive-read with a cursor, so the shared port is hit until
    every one of the N worker pids has answered (or the attempt budget
    runs out — a missed worker is REPORTED, never silently absent), and
    re-drains of an already-seen pid just merge as incremental spans.
    Returns (shards, drain_errors, api_workers_seen)."""
    shards = {}
    errors = 0

    def merge(sh):
        pid = sh.get("pid")
        cur = shards.get(pid)
        if cur is None:
            shards[pid] = sh
        else:
            cur["spans"] = list(cur.get("spans", ())) + \
                list(sh.get("spans", ()))
            cur["dropped"] = int(cur.get("dropped", 0)) + \
                int(sh.get("dropped", 0))
            cur["written"] = max(int(cur.get("written", 0)),
                                 int(sh.get("written", 0)))
        return pid

    api_pids = set()
    for _ in range(max(8, 16 * n_api)):
        if len(api_pids) >= n_api:
            break
        try:
            api_pids.add(merge(json.loads(urllib.request.urlopen(
                f"{master}/debug/trace", timeout=10).read())))
        except Exception:
            errors += 1
    for port in ports:
        try:
            merge(json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/trace", timeout=10).read()))
        except Exception:
            errors += 1
    return list(shards.values()), errors, len(api_pids)


def _scrape_preemption(ports) -> dict:
    """kube-preempt evidence merged across scheduler workers: evict+bind
    commits, victims, per-item CAS losses, the MUST-BE-ZERO
    equal-or-higher-eviction invariant counter, and the preempt-to-bind
    latency quantiles (scheduler_preemption_bind_seconds) — the storm
    record's ``preemption`` section (required when priority_storm)."""
    out = {"attempts": 0, "victims": 0, "conflicts": 0,
           "higher_evictions": 0}
    total, count, bmap = 0.0, 0.0, {}
    for port in ports:
        raw = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
        for key, field in (
                ("scheduler_preemption_attempts_total", "attempts"),
                ("scheduler_preemption_victims_total", "victims"),
                ("scheduler_preemption_conflicts_total", "conflicts"),
                ("scheduler_preemption_higher_evictions_total",
                 "higher_evictions")):
            for line in raw.splitlines():
                if line.startswith(key + " "):
                    out[field] += int(float(line.rsplit(None, 1)[1]))
        s, c, buckets = _parse_hist(raw, "scheduler_preemption_bind_seconds")
        total += s
        count += c
        for le, n in buckets:
            bmap[le] = bmap.get(le, 0.0) + n
    buckets = sorted(bmap.items())
    out["bind_count"] = int(count)
    out["bind_mean_s"] = round(total / count, 4) if count else None
    out["bind_p50_s"] = round(
        _hist_quantile(buckets, count, 0.5), 4) if count else None
    out["bind_p95_s"] = round(
        _hist_quantile(buckets, count, 0.95), 4) if count else None
    return out


def _scrape_defrag(port: int) -> dict:
    """kube-defrag evidence from the descheduler's --metrics-port: wave
    and migration counters, the drain/empty node counts, the declined
    histogram, and the MUST-BE-ZERO score-regression invariant — the
    fragment-storm record's ``fragmentation`` section core (the harness
    adds its own independently computed score_before/score_after)."""
    raw = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
    out = {"waves": 0, "migrations_committed": 0, "migrations_409": 0,
           "nodes_drained": 0, "nodes_emptied": 0, "score_regressions": 0,
           "declined": {}}
    for key, field in (("defrag_waves_total", "waves"),
                       ("defrag_migrations_total", "migrations_committed"),
                       ("defrag_conflicts_total", "migrations_409"),
                       ("defrag_nodes_drained_total", "nodes_drained"),
                       ("defrag_nodes_emptied_total", "nodes_emptied"),
                       ("defrag_score_regressions_total",
                        "score_regressions")):
        for line in raw.splitlines():
            if line.startswith(key + " "):
                out[field] += int(float(line.rsplit(None, 1)[1]))
    for line in raw.splitlines():
        if line.startswith('defrag_declined_total{reason="'):
            reason = line.split('reason="', 1)[1].split('"', 1)[0]
            out["declined"][reason] = \
                out["declined"].get(reason, 0) \
                + int(float(line.rsplit(None, 1)[1]))
    return out


def _frag_score(client, api) -> dict:
    """Harness-side fragmentation score: the pure-python twin of
    models/defrag.fragmentation_score computed from a LIST of truth —
    sum over non-empty nodes of free-permille across the core dims
    (cpu milli-units, memory bytes), lower = better packed. Independent
    of the descheduler's own gauge, so the record's before/after claim
    does not rest on the subsystem it is judging. Also returns the
    resident pod count per node (the drain check) and the unbound pod
    count (the no-half-moves check: an evict whose bind never applied
    would strand a pod here)."""
    nodes = client.nodes().list().items
    pods = client.pods(api.NamespaceAll).list().items
    used: dict = {}
    resident: dict = {}
    unbound = 0
    for p in pods:
        if p.status.phase in (api.PodSucceeded, api.PodFailed):
            continue
        host = p.status.host or p.spec.host
        if not host:
            unbound += 1
            continue
        cpu = mem = 0
        for c in p.spec.containers:
            for name, q in c.resources.limits.items():
                if name == api.ResourceCPU:
                    cpu += q.milli_value()
                elif name == api.ResourceMemory:
                    mem += int(q.value)
        u = used.setdefault(host, [0, 0])
        u[0] += cpu
        u[1] += mem
        resident[host] = resident.get(host, 0) + 1
    score = 0
    for n in nodes:
        name = n.metadata.name
        if not resident.get(name):
            continue
        u = used.get(name, [0, 0])
        for i, res in enumerate((api.ResourceCPU, api.ResourceMemory)):
            q = (n.spec.capacity or {}).get(res)
            if q is None:
                continue
            cap = q.milli_value() if res == api.ResourceCPU \
                else int(q.value)
            if cap <= 0:
                continue
            score += max(cap - u[i], 0) * 1000 // cap
    return {"score": int(score), "resident": resident, "unbound": unbound}


def _scrape_unschedulable(ports) -> dict:
    """kube-explain evidence merged across scheduler workers: the
    unschedulable-pod count, the dominant-reason histogram
    (scheduler_unschedulable_total{reason=...}), the explain layer's
    own cost (invocations, CPU seconds, skips), and the async event
    recorder's posted/dropped disclosure — the record's
    ``unschedulable`` section (required r13+)."""
    out = {"pods": 0, "explain_invocations": 0, "explain_seconds": 0.0,
           "explain_skipped": 0, "events_posted": 0, "events_dropped": 0}
    reasons: dict = {}
    for port in ports:
        raw = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
        for line in raw.splitlines():
            if not line:
                continue
            val = line.rsplit(None, 1)[-1]
            if line.startswith("scheduler_unschedulable_pods_total "):
                out["pods"] += int(float(val))
            elif line.startswith("scheduler_unschedulable_total{"):
                reason = line.split('reason="', 1)[1].split('"', 1)[0]
                reasons[reason] = reasons.get(reason, 0) + int(float(val))
            elif line.startswith("scheduler_explain_invocations_total "):
                out["explain_invocations"] += int(float(val))
            elif line.startswith("scheduler_explain_seconds_total "):
                out["explain_seconds"] += float(val)
            elif line.startswith("scheduler_explain_skipped_total{"):
                out["explain_skipped"] += int(float(val))
            elif line.startswith("event_recorder_posted_total "):
                out["events_posted"] += int(float(val))
            elif line.startswith("event_recorder_dropped_total{"):
                out["events_dropped"] += int(float(val))
    out["explain_seconds"] = round(out["explain_seconds"], 4)
    out["reasons"] = reasons
    return out


def _wave_stats_delta(start: dict, end: dict) -> dict:
    """Steady-state per-wave stats: END minus the post-warmup BASELINE, so
    the once-per-bucket XLA compiles paid during warmup don't pollute the
    timed phase's mean/median."""
    out = {}
    for which in ("encode", "solve", "commit"):
        b0 = dict(start.get(which, ([], 0, 0))[0])
        b1, s1, c1 = end.get(which, ([], 0, 0))
        _, s0, c0 = start.get(which, ([], 0, 0))
        count = c1 - c0
        total = s1 - s0
        if count <= 0:
            continue
        buckets = sorted((le, n - b0.get(le, 0.0)) for le, n in b1)

        def quantile(q: float) -> float:
            target = q * count
            prev_le, prev_n = 0.0, 0.0
            for le, n in buckets:
                if n >= target:
                    if le == float("inf"):
                        return prev_le
                    span = n - prev_n
                    frac = (target - prev_n) / span if span else 1.0
                    return prev_le + (le - prev_le) * frac
                prev_le, prev_n = le, n
            return prev_le

        out[which] = {
            "waves": int(count),
            "mean_ms": round(total / count * 1000, 2),
            "p50_ms": round(quantile(0.5) * 1000, 2),
            "p95_ms": round(quantile(0.95) * 1000, 2),
        }
    return out


def main(argv=None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    if argv and argv[0] == "--_feed":
        return feed(argv[1], int(argv[2]), float(argv[3]), argv[4],
                    replay=argv[5] if len(argv) > 5 else "",
                    depth=int(argv[6]) if len(argv) > 6 else 32,
                    priority_class=argv[7] if len(argv) > 7 else "")

    ap = argparse.ArgumentParser()
    ap.add_argument("--pods", type=int, default=6000)
    ap.add_argument("--rate", type=float, default=1000.0)
    ap.add_argument("--nodes", type=int, default=500)
    ap.add_argument("--feeders", type=int, default=4)
    ap.add_argument("--apiservers", type=int, default=3,
                    help="apiserver worker processes sharing the listen "
                    "port (SO_REUSEPORT) and one kube-store process; 1 = "
                    "single apiserver with its own in-process store")
    ap.add_argument("--schedulers", type=int, default=1,
                    help="tpu-batch scheduler worker processes; losers of "
                    "a bind CAS race requeue, so any N is correct")
    ap.add_argument("--solverd", action="store_true",
                    help="spawn a shared kube-solverd daemon and point "
                    "every scheduler worker at it (--solver-addr): waves "
                    "coalesce into batched solves in ONE hot solver "
                    "process instead of N cold in-process ones")
    ap.add_argument("--mesh-devices", type=int, default=0,
                    help="carve the solverd child's CPU backend into N "
                    "virtual devices (XLA_FLAGS="
                    "--xla_force_host_platform_device_count=N) so the "
                    "daemon's device-mesh dispatch has a mesh to shard "
                    "over; 0 inherits the ambient device topology (real "
                    "multi-chip, or a pre-set XLA_FLAGS)")
    ap.add_argument("--mesh", choices=("auto", "on", "off"), default="auto",
                    help="kube-solverd --mesh: device-mesh production "
                    "dispatch for waves above the node floor (auto = on "
                    "whenever >1 device is attached)")
    ap.add_argument("--pods-axis", type=int, default=1,
                    help="kube-solverd --pods-axis (mesh 'pods' axis)")
    ap.add_argument("--mesh-dispatch",
                    choices=("auto", "shard", "single"), default="auto",
                    help="kube-solverd --mesh-dispatch: auto times "
                    "sharded vs single-device once per shape and runs "
                    "the winner; shard/single pin a layout")
    ap.add_argument("--mesh-min-nodes", type=int, default=0,
                    help="kube-solverd --mesh-min-nodes override (0 = "
                    "daemon default): lets sub-floor shapes — e.g. the "
                    "priority-storm cluster — run through the mesh "
                    "executor's device-resident plane path")
    ap.add_argument("--solver-fallback", "--solver_fallback",
                    choices=("inprocess", "requeue"), default="inprocess",
                    help="pass through to every kube-scheduler worker "
                    "(--solver-fallback): chaos runs that kill solverd "
                    "use 'requeue' so the outage costs seconds of "
                    "requeued waves, not minutes of cold in-process "
                    "compile at full shape — the supervisor respawns "
                    "the daemon anyway")
    ap.add_argument("--prewarm", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="kube-slipstream: boot every scheduler (and "
                    "solverd) with --prewarm so the shape-bucket set "
                    "implied by --nodes/--warm-max-bucket compiles off "
                    "the wave loop, and gate the load window on the "
                    "compile_prewarm_ready gauge instead of the old "
                    "max(180, nodes*0.05) sleep heuristic (kept only "
                    "as the hard timeout). --no-prewarm restores the "
                    "pre-r19 cold-compile warmup.")
    ap.add_argument("--solverd-gather", type=float, default=0.003,
                    help="kube-solverd gather window seconds; raise it "
                    "when several scheduler workers share the daemon so "
                    "their waves coalesce into one vmap call instead of "
                    "serializing through the solve thread")
    ap.add_argument("--watchers", type=int, default=0,
                    help="observer watch streams on /api/v1/pods (the "
                    "kubelet/controller stand-ins every real cluster "
                    "has): each receives every pod event, so the "
                    "encode-once fan-out is exercised at width instead "
                    "of the minimum the scheduler alone provides")
    ap.add_argument("--wave-period", type=float, default=0.1,
                    help="scheduler wave linger seconds: longer waves "
                    "amortize the fixed per-wave cost (drain + HTTP "
                    "commit round-trip) over more pods; shorter waves "
                    "cut per-pod latency. The contract runs measure "
                    "sustained throughput, so the default leans large")
    ap.add_argument("--depth", type=int, default=32,
                    help="per-feeder pipelined requests in flight; the "
                    "offered rate is bounded by depth x feeders / server "
                    "latency, so a latency-bound run needs more depth, "
                    "not more feeder CPU")
    ap.add_argument("--trace", action="store_true",
                    help="kube-trace: run every child (--trace on "
                    "apiservers, schedulers, solverd), drain each "
                    "process's /debug/trace span ring at the end of the "
                    "run, and merge the shards on the shared monotonic "
                    "clock into ONE Chrome-trace-event / "
                    "Perfetto-loadable JSON artifact next to --out")
    ap.add_argument("--trace-device", default="",
                    help="pass through to kube-solverd --trace-device: "
                    "jax.profiler device trace directory (empty "
                    "disables)")
    ap.add_argument("--flightrec", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="kube-flightrec (default ON, r11+ records "
                    "require it): run every control-plane child with "
                    "--flightrec, pull each process's /debug/vars "
                    "time-series shard incrementally through a live "
                    "FlightAggregator, evaluate the churn SLO rule set "
                    "during the run, and emit the timeline + alarms "
                    "record sections plus the full-resolution "
                    "<out>_timeline.json sidecar")
    ap.add_argument("--flightrec-poll", type=float, default=2.0,
                    help="aggregator pull period, seconds (children "
                    "sample their rings at 1 s regardless)")
    ap.add_argument("--rss-ceiling-gb", type=float, default=8.0,
                    help="per-process RSS SLO ceiling, GiB")
    ap.add_argument("--binds-floor", type=float, default=50.0,
                    help="sustained binds/s SLO floor while load is "
                    "offered")
    ap.add_argument("--lag-storm", type=int, default=0,
                    help="induce a watcher-lag storm: N deliberately "
                    "throttled observer watch streams (tiny reads, long "
                    "sleeps) whose queues must blow the apiserver's "
                    "--watch-lag-limit and 410-resync — the watch-lag "
                    "SLO alarm demonstration")
    ap.add_argument("--watch-lag-limit", type=int, default=0,
                    help="pass through to the apiserver(s); 0 keeps the "
                    "server default (65536). Lag-storm runs set this "
                    "low so the storm trips inside the run's span")
    ap.add_argument("--priority-storm", action="store_true",
                    help="kube-preempt scenario: pre-fill the cluster "
                    "EXACTLY to capacity with low-priority pods "
                    "(PriorityClass storm-low), then offer --pods "
                    "high-priority pods (storm-high) at --rate — every "
                    "storm pod must bind via atomic evict+bind "
                    "preemption. Nodes are sized to "
                    "--storm-fill-per-node template pods; the record "
                    "gains a priority_storm marker + preemption section "
                    "and perfgate isolates it from the clean series")
    ap.add_argument("--storm-fill-per-node", type=int, default=8,
                    help="template pods per node at exact capacity in "
                    "--priority-storm mode")
    ap.add_argument("--fragment-storm", action="store_true",
                    help="kube-defrag scenario: the bursty feed leaves "
                    "the template pods smeared thin across every node "
                    "(the fragmented steady state); once all pods are "
                    "bound the harness cordons --storm-cordon nodes and "
                    "a kube-descheduler child (spawned alongside the "
                    "schedulers, declining waves while the feed's "
                    "unbound pods exist) consolidates: cordoned nodes "
                    "drain, sparse nodes empty, the fragmentation score "
                    "measurably drops. The record gains a fragmentation "
                    "section (score before/after, migrations committed/"
                    "409'd, nodes drained/emptied, 0 half-moves) and "
                    "perfgate isolates the +fragmentstorm shape")
    ap.add_argument("--storm-cordon", type=int, default=8,
                    help="nodes cordoned (spec.unschedulable) after the "
                    "feed in --fragment-storm mode; all must fully "
                    "drain via mandatory migrations")
    ap.add_argument("--defrag-window", type=float, default=120.0,
                    help="max seconds to wait for the defrag waves to "
                    "drain the cordoned set and go quiescent in "
                    "--fragment-storm mode")
    ap.add_argument("--defrag-max-moves", type=int, default=50,
                    help="kube-descheduler --max-moves (voluntary "
                    "migrations per wave) in --fragment-storm mode")
    ap.add_argument("--overload", action="store_true",
                    help="kube-fairshed overload scenario: offer --rate "
                    "(set it ≥ 2x the sustained capacity) into a "
                    "fairshed-governed apiserver with the workload "
                    "backlog limiter armed (--fairshed-backlog, default "
                    "2500 in this mode). Excess creates shed with "
                    "429 + measured-drain Retry-After; feeders honor it "
                    "and resume from the acked prefix, so every pod is "
                    "eventually admitted but the created-but-unbound "
                    "backlog — the 37 s invisible e2e queue of the "
                    "unprotected baseline — stays bounded. The record "
                    "gains overload + fairshed sections (sheds REQUIRED "
                    "and disclosed; system-flow sheds must be 0) and "
                    "perfgate isolates the +overload shape. Works at "
                    "any --apiservers N: a reuseport fleet aggregates "
                    "its ledger through the kube-share segment "
                    "(apiserver/share.py), keeping the governor and "
                    "Retry-After hints exact across workers.")
    ap.add_argument("--fairshed-backlog", "--fairshed_backlog", type=int,
                    default=0,
                    help="pass through to the apiserver(s): shed "
                    "workload pod creates once created-but-unbound "
                    "exceeds this (0 keeps the governor off outside "
                    "--overload)")
    ap.add_argument("--chaos", default="",
                    help="kube-chaos kill schedule: comma-separated "
                    "component@TIME[s][:SIGNAL] entries, e.g. "
                    "'apiserver@120s,solverd@240s:SIGKILL,"
                    "scheduler@300s,kube-store@360s'. Times are seconds "
                    "after the feeders launch; default signal SIGKILL. "
                    "Every supervised child that dies — scheduled or "
                    "organic — is respawned, counted, and its "
                    "respawn-to-ready time recorded; the record gains "
                    "chaos + store sections and perfgate isolates the "
                    "+chaos shape from the clean series")
    ap.add_argument("--store-data-dir", "--store_data_dir", default="",
                    help="kube-store --data-dir: persist the cluster "
                    "store (DurableStore WAL + snapshots) so a killed "
                    "kube-store recovers; with --apiservers 1 the "
                    "apiserver's in-process store persists instead. A "
                    "--chaos schedule that kills the store requires it.")
    ap.add_argument("--store-compact-every", "--store_compact_every",
                    type=int, default=10_000,
                    help="kube-store --compact-every (snapshot + WAL "
                    "truncate period, records)")
    ap.add_argument("--store-fsync", action="store_true",
                    help="kube-store --fsync (media-crash durability; "
                    "default flush-only survives process kill)")
    ap.add_argument("--store-shards", "--store_shards", type=int,
                    default=1,
                    help="kube-stripe: shard the store keyspace by "
                    "namespace hash into this many shards (power of "
                    "two; per-shard locks, rings and watcher lists "
                    "under one global revision counter). Passed to "
                    "kube-store (--apiservers > 1) or the apiserver's "
                    "in-process store. 1 = the unsharded twin.")
    ap.add_argument("--warm-max-bucket", "--warm_max_bucket", type=int,
                    default=1024,
                    help="largest pow-2 wave bucket compiled during "
                    "warmup; small harness runs (the chaos e2e test) "
                    "drop it to skip compiles their shape never uses")
    ap.add_argument("--bound-timeout", type=float, default=180.0,
                    help="seconds to wait for all pods bound after the "
                    "feed; chaos runs need headroom for recovery "
                    "windows and post-outage backlog")
    ap.add_argument("--port", type=int, default=18410)
    ap.add_argument("--out", default=None)
    ap.add_argument("--platform", choices=["cpu", "ambient"], default="cpu",
                    help="solver backend: cpu (default; the churn "
                    "contract measures the control plane) or ambient: "
                    "ONE child inherits this environment and with it "
                    "the chip — solverd under --solverd, else the single "
                    "scheduler — and every other child stays on the CPU "
                    "backend. An ambient run in which a scheduler solved "
                    "a wave in-process beside solverd fails.")
    args = ap.parse_args(argv)
    master = f"http://127.0.0.1:{args.port}"
    try:
        chip_owner = chip_child(args.platform, args.solverd,
                                args.schedulers)
    except ValueError as e:
        ap.error(str(e))
    child_env = cpu_env()

    procs = []   # (name, Popen) — names feed the per-stage CPU budget

    logdir = "/tmp/churn_mp_logs"
    os.makedirs(logdir, exist_ok=True)

    import socket as socket_mod
    import threading

    # -- kube-chaos supervision (docs/design/ha.md) ---------------------
    # EVERY control-plane child registers a readiness probe and is
    # respawned if it dies — scheduled kill or organic crash alike
    # (generalizing the bespoke solverd supervisor PR 7 shipped).
    # Restarts and respawn-to-ready times are counted into the record
    # AND into the parent's own metric registry, which rides the
    # flightrec timeline as the 'harness' target so the
    # component_restart / recovery_time_ceiling SLO rules judge the
    # outages live.
    supervised = {}       # name -> {"cmd", "env", "ready"}
    restarts = {}         # name -> respawn count
    recovery_times = {}   # name -> [respawn-to-ready seconds, ...]
    recovery_timeouts = {}  # name -> ready-waits that never completed
    supervise_stop = threading.Event()
    _spawned_names = set()

    def spawn(name, *cmd, env=None, ready=None):
        # append on respawn: the pre-kill log is crash evidence
        mode = "a" if name in _spawned_names else "w"
        _spawned_names.add(name)
        log = open(os.path.join(logdir, f"{name}.log"), mode)
        env = env or child_env_for(name, chip_owner)
        p = subprocess.Popen(cmd, env=env, stdout=log, stderr=log)
        procs.append((name, p))
        if ready is not None:
            supervised[name] = {"cmd": cmd, "env": env, "ready": ready}
        return p

    def _tcp_ready(port, deadline_s=60.0):
        def ready():
            end = time.monotonic() + deadline_s
            while time.monotonic() < end and not supervise_stop.is_set():
                try:
                    socket_mod.create_connection(
                        ("127.0.0.1", port), timeout=1.0).close()
                    return True
                except OSError:
                    time.sleep(0.2)
            return False
        return ready

    def _http_ready(url, deadline_s=60.0):
        def ready():
            end = time.monotonic() + deadline_s
            while time.monotonic() < end and not supervise_stop.is_set():
                try:
                    urllib.request.urlopen(url, timeout=1.0)
                    return True
                except Exception:
                    time.sleep(0.2)
            return False
        return ready

    from kubernetes_tpu.util import metrics as metrics_pkg
    _chaos_mx = metrics_pkg.chaos_metrics()

    _recovering = set()  # names with a ready-wait in flight

    def _await_ready(name, info, t0r):
        """Readiness watch for one respawn, off the monitor loop: a
        slow boot (jax import, store recovery) must not head-of-line
        block the NEXT component's respawn — a schedule that kills the
        scheduler then kube-store would otherwise leave the store dead
        behind a 60 s ready-wait."""
        try:
            ok_r = info["ready"]()
            rec_s = time.monotonic() - t0r
            if ok_r:
                recovery_times.setdefault(name, []).append(round(rec_s, 2))
                _chaos_mx.recovery_s.observe(rec_s)
            elif not supervise_stop.is_set():
                # a timed-out ready-wait is a FAILED recovery, recorded
                # as such — logging the probe deadline as a recovery
                # time would misstate a wedged respawn as a slow one
                recovery_timeouts[name] = recovery_timeouts.get(name, 0) + 1
                print(f"[churn-mp] ERROR: respawned {name} never "
                      f"became ready", file=sys.stderr, flush=True)
        finally:
            _recovering.discard(name)

    def _supervise():
        while not supervise_stop.wait(0.5):
            for name, info in list(supervised.items()):
                if name in _recovering:
                    continue  # its respawn's ready-wait is in flight
                _n, p = next(np_ for np_ in reversed(procs)
                             if np_[0] == name)
                if p.poll() is None:
                    continue
                if supervise_stop.is_set():
                    return  # teardown began after this tick's wait
                restarts[name] = restarts.get(name, 0) + 1
                _chaos_mx.restarts.inc()
                print(f"[churn-mp] WARNING: {name} exited "
                      f"rc={p.returncode}; respawning "
                      f"(restart #{restarts[name]})",
                      file=sys.stderr, flush=True)
                t0r = time.monotonic()
                _recovering.add(name)
                spawn(name, *info["cmd"], env=info["env"],
                      ready=info["ready"])
                threading.Thread(
                    target=_await_ready, args=(name, info, t0r),
                    daemon=True,
                    name=f"chaos-ready-{name}").start()

    chaos_events = parse_chaos(args.chaos) if args.chaos else []
    kill_log = []
    run_window = threading.Event()  # set while offered load/drain runs

    def _killer(t_base):
        import signal as signal_mod
        for ev in chaos_events:
            delay = t_base + ev["t_s"] - time.monotonic()
            if delay > 0 and supervise_stop.wait(delay):
                return
            if not run_window.is_set():
                # the run completed (or aborted) before this kill's
                # time: disclose the skip — a kill landing during the
                # scrape phase would corrupt evidence, not prove
                # recovery
                kill_log.append(dict(ev, skipped="after run window"))
                continue
            name = ev["component"]
            target = next((np_ for np_ in reversed(procs)
                           if np_[0] == name and np_[1].poll() is None),
                          None)
            if target is None:
                kill_log.append(dict(ev, error="no live process"))
                continue
            try:
                if "delay_s" in ev:
                    # latency injection: a live gray stall of exactly
                    # delay_s — SIGSTOP freezes every thread (requests
                    # queue at the socket, in-flight work suspends),
                    # SIGCONT resumes; the process never dies, so the
                    # supervisor correctly sees nothing to respawn
                    target[1].send_signal(signal_mod.SIGSTOP)
                    time.sleep(ev["delay_s"])
                    target[1].send_signal(signal_mod.SIGCONT)
                    kill_log.append(dict(ev, pid=target[1].pid))
                    print(f"[churn-mp] CHAOS: delay {ev['delay_s']*1000:.0f}"
                          f"ms (SIGSTOP/SIGCONT) -> {name} "
                          f"(pid {target[1].pid}) at t+{ev['t_s']:.0f}s",
                          file=sys.stderr, flush=True)
                    continue
                target[1].send_signal(getattr(signal_mod, ev["signal"]))
                kill_log.append(dict(ev, pid=target[1].pid))
                print(f"[churn-mp] CHAOS: {ev['signal']} -> {name} "
                      f"(pid {target[1].pid}) at t+{ev['t_s']:.0f}s",
                      file=sys.stderr, flush=True)
            except OSError as e:
                kill_log.append(dict(ev, error=repr(e)))

    def cpu_budget() -> dict:
        """utime+stime per stage for every still-running child — the
        'which host stage is the wall' evidence the round target asks
        for. Feeders self-report (they exit before this runs)."""
        agg = {}
        for name, p in procs:
            base = re.sub(r"\d+$", "", name)
            try:
                agg[base] = round(agg.get(base, 0.0)
                                  + _proc_cpu_s(p.pid), 2)
            except (OSError, IndexError, ValueError):
                pass
        return agg

    flight_agg = None  # the in-run kube-flightrec aggregator

    def flush_flightrec(record: dict) -> None:
        """Timeline + alarms into the record (and the full-resolution
        sidecar next to --out) — called on BOTH the success and the
        abort path: the failure runs are exactly the ones where the
        curves matter."""
        if flight_agg is None:
            return
        try:
            flight_agg.stop()  # joins the poll thread + one final pull
            sidecar_path = sidecar_name = ""
            if args.out:
                sidecar_path = re.sub(r"\.json$", "", args.out) \
                    + "_timeline.json"
                sidecar_name = os.path.basename(sidecar_path)
            record["timeline"] = flight_agg.timeline(sidecar=sidecar_name)
            record["alarms"] = flight_agg.alarms()
            if sidecar_path:
                with open(sidecar_path, "w") as f:
                    json.dump(flight_agg.sidecar_payload(), f)
            n_series = len(record["timeline"].get("series", ()))
            firing = [a for a in record["alarms"]
                      if a.get("state") == "firing"]
            print(f"[churn-mp] flightrec: {n_series} headline series, "
                  f"{len(record['alarms'])} alarm transitions "
                  f"({len(firing)} firing)"
                  + (f" -> {sidecar_name}" if sidecar_name else ""),
                  file=sys.stderr, flush=True)
        except Exception as e:
            record["timeline"] = {"error": f"flightrec flush failed: {e}"}
            record.setdefault("alarms", [])

    def _chaos_record_sections(record: dict) -> None:
        """The kube-chaos evidence, on BOTH the success and abort paths
        (the outage runs are exactly the ones where the restart counts
        and recovery times matter): the kill schedule + what actually
        happened, per-component restarts and respawn-to-ready times,
        feeder recovery stats, and the kube-store WAL/recovery scrape."""
        if restarts:
            # organic (unscheduled) deaths are disclosed on every run
            record.setdefault("component_restarts", dict(restarts))
        if not args.chaos:
            if args.store_data_dir and store_metrics_port:
                try:
                    record["store"] = _scrape_store(store_metrics_port)
                except Exception as e:
                    record["store"] = {"error": f"scrape failed: {e}"}
            return
        chaos_sec = {
            "schedule": args.chaos,
            "events": list(kill_log),
            "restarts": {name: restarts.get(name, 0)
                         for name in sorted(
                             {e["component"] for e in chaos_events}
                             | set(restarts))},
            "recovery_s": {k: list(v)
                           for k, v in sorted(recovery_times.items())},
        }
        if recovery_timeouts:
            chaos_sec["recovery_timeouts"] = dict(recovery_timeouts)
        fr = {}
        for s in stats:
            if isinstance(s, dict):
                for k in ("reconnects", "retried_conflicts",
                          "retried_5xx"):
                    fr[k] = fr.get(k, 0) + int(s.get(k, 0))
        chaos_sec["feeders"] = fr
        record["chaos"] = chaos_sec
        if store_metrics_port:
            try:
                record["store"] = _scrape_store(store_metrics_port)
            except Exception as e:
                record["store"] = {"error": f"scrape failed: {e}"}
        else:
            # single-apiserver topology: the durable store lives inside
            # the apiserver; recovery is disclosed via its /healthz
            try:
                h = json.loads(urllib.request.urlopen(
                    f"{master}/healthz", timeout=5).read())
                record["store"] = {
                    "error": "in-process store (no kube-store metrics)",
                    "recovery": h.get("recovery", {})}
            except Exception as e:
                record["store"] = {"error": f"healthz failed: {e}"}

    if args.overload and not args.fairshed_backlog:
        args.fairshed_backlog = 2500
    api_extra = []
    if args.trace:
        api_extra.append("--trace")
    if args.flightrec:
        api_extra.append("--flightrec")
    if args.watch_lag_limit:
        api_extra += ["--watch-lag-limit", str(args.watch_lag_limit)]
    if args.fairshed_backlog:
        api_extra += ["--fairshed-backlog", str(args.fairshed_backlog)]
    store_metrics_port = 0
    share_seg_path = ""
    try:
        # chaos schedules may only name components this topology runs
        valid = {f"apiserver{w}" for w in range(args.apiservers)} \
            | {f"scheduler{w}" for w in range(args.schedulers)} \
            | ({"solverd"} if args.solverd else set()) \
            | ({"storeserver"} if args.apiservers > 1 else set())
        if args.apiservers == 1:
            valid.add("apiserver0")  # alias for the single apiserver
        for ev in chaos_events:
            if ev["component"] not in valid:
                raise RuntimeError(
                    f"--chaos names {ev['component']!r}, which this "
                    f"topology does not run (valid: {sorted(valid)})")
        if any(ev["component"] == "storeserver" and "signal" in ev
               for ev in chaos_events) and not args.store_data_dir:
            raise RuntimeError(
                "--chaos kills kube-store but --store-data-dir is "
                "unset: the cluster state would not survive the kill")
        if args.apiservers > 1:
            # reference topology at scale: one store process (etcd analog)
            # + N apiserver workers sharing the port via SO_REUSEPORT
            store_port = args.port + 1
            store_metrics_port = args.port + 2
            store_cmd = [PY, "-m", "kubernetes_tpu.cmd.storeserver",
                         "--port", str(store_port),
                         "--metrics-port", str(store_metrics_port)]
            if args.store_shards > 1:
                store_cmd += ["--shards", str(args.store_shards)]
            if args.store_data_dir:
                os.makedirs(args.store_data_dir, exist_ok=True)
                store_cmd += ["--data-dir", args.store_data_dir,
                              "--compact-every",
                              str(args.store_compact_every)]
                if args.store_fsync:
                    store_cmd.append("--fsync")
            if args.flightrec:
                store_cmd.append("--flightrec")
            spawn("storeserver", *store_cmd,
                  ready=_tcp_ready(store_port))
            # kube-share segment (apiserver/share.py): cross-process
            # frame-cache seeding + the cross-worker fairshed ledger
            # that keeps the backlog governor exact at N workers
            from kubernetes_tpu.apiserver.share import ShareSegment
            share_dir = "/dev/shm" if os.path.isdir("/dev/shm") \
                else tempfile.gettempdir()
            share_seg_path = os.path.join(
                share_dir, f"ktpu-share-{os.getpid()}.seg")
            ShareSegment.create(share_seg_path, args.apiservers).close()
            for w in range(args.apiservers):
                spawn(f"apiserver{w}", PY, "-m",
                      "kubernetes_tpu.cmd.apiserver",
                      "--port", str(args.port), "--reuse-port",
                      "--store-server", f"127.0.0.1:{store_port}",
                      "--share-seg", share_seg_path,
                      "--share-worker", str(w),
                      *api_extra,
                      ready=_http_ready(f"{master}/healthz/ping"))
        else:
            api_cmd = [PY, "-m", "kubernetes_tpu.cmd.apiserver",
                       "--port", str(args.port), *api_extra]
            if args.store_data_dir:
                os.makedirs(args.store_data_dir, exist_ok=True)
                api_cmd += ["--data-dir", args.store_data_dir]
            if args.store_shards > 1:
                api_cmd += ["--store-shards", str(args.store_shards)]
            spawn("apiserver0", *api_cmd,
                  ready=_http_ready(f"{master}/healthz/ping"))
        deadline = time.time() + 60
        while time.time() < deadline:
            try:
                urllib.request.urlopen(f"{master}/healthz", timeout=1)
                break
            except Exception:
                time.sleep(0.3)
        else:
            raise RuntimeError("apiserver never became healthy")

        from kubernetes_tpu.api import types as api
        from kubernetes_tpu.api.quantity import Quantity
        from kubernetes_tpu.client.client import Client
        from kubernetes_tpu.client.http import HTTPTransport
        client = Client(HTTPTransport(master))
        if args.priority_storm:
            # kube-preempt: nodes sized to EXACTLY --storm-fill-per-node
            # template pods (100m / 128Mi each), so "full" is a precise
            # number; the two PriorityClasses drive admission resolution
            fpn = args.storm_fill_per_node
            node_cap = {"cpu": Quantity(f"{fpn * 100}m"),
                        "memory": Quantity(f"{fpn * 128}Mi")}
            client.resource("priorityclasses").create(api.PriorityClass(
                metadata=api.ObjectMeta(name="storm-low"), value=100))
            client.resource("priorityclasses").create(api.PriorityClass(
                metadata=api.ObjectMeta(name="storm-high"), value=1000))
        else:
            node_cap = {"cpu": Quantity("64"),
                        "memory": Quantity("256Gi")}
        for i in range(args.nodes):
            client.nodes().create(api.Node(
                metadata=api.ObjectMeta(name=f"node-{i:05d}"),
                spec=api.NodeSpec(capacity=dict(node_cap))))

        # batch-vs-per-pod CAS parity on the LIVE server, before any
        # scheduler can race the probe pods (the zero-divergence evidence
        # the record carries). Skipped in storm mode: probe pods bind
        # directly onto the sized nodes and would break the exact-fill
        # arithmetic the scenario depends on.
        if args.priority_storm:
            parity = {"skipped": "priority-storm (probe pods would "
                                 "consume the exact-fill capacity)"}
            bind_probe = {"skipped": "priority-storm"}
        else:
            try:
                parity = bind_parity_probe(client, api, args.nodes)
            except Exception as e:
                parity = {"error": f"probe failed: {e}"}
            # isolated bind cost on the quiet server (comparable to r07's
            # commit-derived figure, measured on post-feed waves). Sized
            # under the backlog governor when one is armed: the probe's
            # per-round create burst must fit the ceiling or the
            # governor (correctly) sheds the probe itself
            try:
                cap = args.fairshed_backlog or 1 << 30
                bind_probe = bind_cost_probe(
                    client, api, args.nodes,
                    k=min(512, max(1, cap // 2)),
                    per_pod_n=min(256, max(1, cap // 2)))
            except Exception as e:
                bind_probe = {"error": f"probe failed: {e}"}

        solver_addr = ""
        if args.solverd:
            solverd_port = args.port + 7
            solver_addr = f"127.0.0.1:{solverd_port}"
            solverd_metrics_port = args.port + 8
            sd_env = child_env_for("solverd", chip_owner)
            if args.mesh_devices:
                # carve the daemon's CPU backend into a virtual device
                # mesh; the other children keep the plain single-device
                # backend (they never solve when the daemon is healthy)
                flags = sd_env.get("XLA_FLAGS", "")
                sd_env["XLA_FLAGS"] = (
                    (flags + " " if flags else "")
                    + "--xla_force_host_platform_device_count="
                    + str(args.mesh_devices))
            spawn("solverd", PY, "-m", "kubernetes_tpu.cmd.solverd",
                  "--port", str(solverd_port),
                  "--gather-window", str(args.solverd_gather),
                  "--metrics-port", str(solverd_metrics_port),
                  "--mesh", args.mesh,
                  "--pods-axis", str(args.pods_axis),
                  "--mesh-dispatch", args.mesh_dispatch,
                  *(["--mesh-min-nodes", str(args.mesh_min_nodes)]
                    if args.mesh_min_nodes else []),
                  *(["--prewarm",
                     "--prewarm-nodes", str(args.nodes),
                     "--prewarm-pods", str(args.warm_max_bucket),
                     "--prewarm-batch", str(args.schedulers)]
                    if args.prewarm else []),
                  *(["--trace"] if args.trace else []),
                  *(["--flightrec"] if args.flightrec else []),
                  *(["--trace-device", args.trace_device]
                    if args.trace_device else []),
                  env=sd_env,
                  # supervised like every other child (the bespoke
                  # solverd respawner PR 7 shipped, generalized): a
                  # daemon that dies mid-run — scheduled kill or native
                  # crash — is respawned instead of leaving every
                  # scheduler in the in-process fallback for the rest
                  # of the run; the RemoteSolver cooldown reconnects
                  # within seconds and the delta wire resyncs with one
                  # full frame. Restarts are DISCLOSED in the record.
                  ready=_tcp_ready(solverd_port))
            # the daemon must own its socket before any worker's first
            # wave, or every worker starts in the fallback cooldown
            if not _tcp_ready(solverd_port, deadline_s=30.0)():
                raise RuntimeError("kube-solverd never came up")

        sched_metrics_ports = [args.port + 9 + w
                               for w in range(args.schedulers)]
        for w in range(args.schedulers):
            cmd = [PY, "-m", "kubernetes_tpu.cmd.scheduler",
                   "--master", master, "--algorithm", "tpu-batch",
                   "--wave-period", str(args.wave_period),
                   "--metrics-port", str(sched_metrics_ports[w])]
            if solver_addr:
                cmd += ["--solver-addr", solver_addr,
                        "--solver-fallback", args.solver_fallback]
            if args.prewarm:
                # with --solver-addr the shared programs live in solverd
                # (whose own --prewarm covers them); the scheduler then
                # reports compile_prewarm_ready=1 immediately
                cmd += ["--prewarm"]
            if args.trace:
                cmd += ["--trace"]
            if args.flightrec:
                cmd += ["--flightrec"]
            spawn(f"scheduler{w}", *cmd,
                  ready=_http_ready(f"http://127.0.0.1:"
                                    f"{sched_metrics_ports[w]}"
                                    f"/healthz/ping"))

        desched_metrics_port = 0
        if args.fragment_storm:
            # the descheduler rides along from boot: it declines every
            # wave while the feed's unbound pods exist (pending_work —
            # the scheduler owns the churn budget), then consolidates
            # once the cluster is quiescent. period/qps are tight here
            # because the harness WAITS on the waves; production
            # defaults are far lazier.
            desched_metrics_port = args.port + 9 + args.schedulers
            # qps 0.5 x max-moves 50 bounds sustained migrations at
            # 25/s — half the defrag_migration_storm SLO ceiling, so a
            # conformant run proves the pacing, not just the drain
            dcmd = [PY, "-m", "kubernetes_tpu.cmd.descheduler",
                    "--master", master, "--period", "0.5",
                    "--qps", "0.5", "--burst", "1",
                    "--max-moves", str(args.defrag_max_moves),
                    "--metrics-port", str(desched_metrics_port)]
            if args.flightrec:
                dcmd += ["--flightrec"]
            spawn("descheduler", *dcmd,
                  ready=_http_ready(f"http://127.0.0.1:"
                                    f"{desched_metrics_port}"
                                    f"/healthz/ping"))

        # every child is registered: the supervisor watches from here
        threading.Thread(target=_supervise, daemon=True,
                         name="chaos-supervisor").start()

        harness_port = 0
        if args.flightrec:
            # the live aggregator: discovers every control-plane process
            # (incl. all SO_REUSEPORT apiserver worker pids via the
            # drain-until-all-pids-answer pattern), pulls /debug/vars
            # incrementally, and evaluates the churn SLO set during the
            # run — alarms fire live, not in post-mortem
            from kubernetes_tpu.addons.monitoring import (
                FlightAggregator,
                default_churn_rules,
            )
            targets = [{"name": "apiserver", "url": master,
                        "workers": args.apiservers}]
            targets += [{"name": f"scheduler{w}",
                         "url": f"http://127.0.0.1:{p}"}
                        for w, p in enumerate(sched_metrics_ports)]
            if solver_addr:
                targets.append({"name": "solverd",
                                "url": f"http://127.0.0.1:"
                                       f"{solverd_metrics_port}"})
            if store_metrics_port:
                # kube-store's WAL/recovery series ride the timeline too
                targets.append({"name": "storeserver",
                                "url": f"http://127.0.0.1:"
                                       f"{store_metrics_port}"})
            if desched_metrics_port:
                # the defrag_* family rides the timeline so the
                # defrag_migration_storm / monotone-score SLO rules
                # judge the waves live
                targets.append({"name": "descheduler",
                                "url": f"http://127.0.0.1:"
                                       f"{desched_metrics_port}"})
            # the harness itself is a target: the supervisor's
            # component_restarts_total / component_recovery_seconds live
            # in THIS process's registry, and the SLO rules judging the
            # outages need them on the merged timeline
            from kubernetes_tpu.cmd.scheduler import _serve_debug
            metrics_pkg.flightrec_arm("harness", period_s=1.0)
            harness_port = args.port + 3
            _serve_debug(harness_port, service="harness")
            targets.append({"name": "harness",
                            "url": f"http://127.0.0.1:{harness_port}"})
            flight_agg = FlightAggregator(
                targets,
                rules=default_churn_rules(
                    binds_floor=args.binds_floor,
                    rss_ceil_bytes=args.rss_ceiling_gb * (1 << 30),
                    # the admitted-e2e ceiling only makes sense when the
                    # backlog governor bounds the pending queue; an
                    # ungoverned contract run legitimately backlogs past
                    # it (r11: 37 s) and must keep its alarms-[] claim
                    admitted_e2e_ceil_s=(
                        10.0 if args.fairshed_backlog else None)),
                period_s=args.flightrec_poll).start()

        # Bind counting rides a WATCH, not list polling: a full
        # field-selected LIST costs O(all pods) server CPU per poll
        # (~0.6s at 50k pods — the monitor would eat the core it is
        # trying to measure). A pod transitioning into the
        # spec.host!= filter emits one ADDED frame; counting frames on
        # the raw chunked stream costs the server one cached frame
        # encode and this process a substring scan. If the stream ever
        # ends (a 410 lag drop, an apiserver hiccup), the monitor does
        # what any reflector does: ONE list to resync the count, then
        # re-watches from the list's resourceVersion — bound pods never
        # unbind, so frames-seen and bound-now stay the same number.
        import socket as socketlib
        import threading as threadinglib
        bound_count = [0]
        # pods the probes bound before the monitor started (a resync LIST
        # would count them; the watch stream never does)
        parity_bound = (parity.get("checked", 2) - 2
                        + bind_probe.get("pods", 0))
        churn_done = threadinglib.Event()

        MARK = b'"type": "ADDED"'

        def _count_stream(rv: str) -> None:
            q = b"watch=1&fieldSelector=spec.host%21%3D"
            if rv:
                q += b"&resourceVersion=" + rv.encode()
            s = socketlib.create_connection(("127.0.0.1", args.port))
            try:
                s.sendall(b"GET /api/v1/pods?" + q +
                          b" HTTP/1.1\r\nHost: a\r\n\r\n")
                tail = b""
                while True:
                    chunk = s.recv(1 << 16)
                    if not chunk:
                        return
                    buf = tail + chunk
                    n = buf.count(MARK)
                    if n:
                        bound_count[0] += n
                        # drop everything through the last counted marker
                        # so the kept tail can never be re-counted
                        buf = buf[buf.rfind(MARK) + len(MARK):]
                    tail = buf[-(len(MARK) - 1):]  # split marker survives
            finally:
                s.close()

        def bind_counter():
            rv = ""
            while not churn_done.is_set():
                try:
                    _count_stream(rv)
                except OSError:
                    pass
                if churn_done.is_set():
                    return
                # stream ended: resync count from one list, resume from
                # its resourceVersion (the Reflector contract)
                try:
                    lst = json.loads(urllib.request.urlopen(
                        f"{master}/api/v1/pods?fieldSelector="
                        "spec.host%21%3D", timeout=30).read())
                    bound_count[0] = len(lst.get("items", ())) - parity_bound
                    rv = str(lst.get("metadata", {})
                             .get("resourceVersion", ""))
                except Exception:
                    time.sleep(0.5)

        threadinglib.Thread(target=bind_counter, daemon=True).start()

        # observer fleet: each stream receives every pod frame as cached
        # bytes (a stand-in for the kubelets/controllers of a real
        # cluster); readers just drain and count
        observer_frames = [0] * args.watchers

        def observer(slot):
            while not churn_done.is_set():
                try:
                    s = socketlib.create_connection(("127.0.0.1", args.port))
                    s.sendall(b"GET /api/v1/pods?watch=1 HTTP/1.1\r\n"
                              b"Host: a\r\n\r\n")
                    while True:
                        chunk = s.recv(1 << 16)
                        if not chunk:
                            break
                        observer_frames[slot] += chunk.count(b'"type"')
                    s.close()
                except OSError:
                    time.sleep(0.2)

        for w in range(args.watchers):
            threadinglib.Thread(target=observer, args=(w,),
                                daemon=True).start()

        # induced watcher-lag storm: observers that deliberately cannot
        # keep up (tiny reads, long sleeps). Their per-watcher queues
        # must blow past --watch-lag-limit, take the 410 drop-to-resync,
        # and fire the watch-lag SLO alarm — the live demonstration that
        # the watchdog catches a sick watcher while the run is still
        # going, with the triggering samples in the transition record.
        lag_resyncs_seen = [0] * args.lag_storm

        def throttled_observer(slot):
            while not churn_done.is_set():
                try:
                    s = socketlib.create_connection(("127.0.0.1",
                                                     args.port))
                    s.sendall(b"GET /api/v1/pods?watch=1 HTTP/1.1\r\n"
                              b"Host: a\r\n\r\n")
                    while not churn_done.is_set():
                        chunk = s.recv(2048)
                        if not chunk:
                            break
                        if b'"reason": "Expired"' in chunk:
                            lag_resyncs_seen[slot] += 1
                        time.sleep(0.25)
                    s.close()
                except OSError:
                    time.sleep(0.2)

        for w in range(args.lag_storm):
            threadinglib.Thread(target=throttled_observer, args=(w,),
                                daemon=True).start()

        def wait_all_bound(total_created, timeout=180.0):
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                if bound_count[0] >= total_created:
                    return True
                time.sleep(0.05)
            return False

        # warmup: every pow-2 wave bucket compiles before the clock starts
        print("[churn-mp] warmup (compiling wave buckets)...",
              file=sys.stderr, flush=True)
        warm_total = 0
        size = args.warm_max_bucket
        # XLA compile time for a wave bucket scales with the padded node
        # dimension: 180 s fits the 10k-node contract shape, but planet
        # shapes (40k+ nodes) need the window to scale. Warmup is off
        # the record clock by design, so generous is free.
        warm_wait = max(180.0, args.nodes * 0.05)
        prewarm_compile_s = 0.0
        if args.prewarm:
            # kube-slipstream: the boot prewarm set reports compiled
            # through the compile_prewarm_ready gauge on every scheduler
            # (and solverd when it owns the programs); the node-count
            # formula above survives only as the HARD TIMEOUT on that
            # signal, not as the wait itself.
            t_pw = time.perf_counter()
            pw_ports = list(sched_metrics_ports)
            if args.solverd:
                pw_ports.append(solverd_metrics_port)
            pw_deadline = time.monotonic() + warm_wait
            pw_pending = set(pw_ports)
            while pw_pending and time.monotonic() < pw_deadline:
                for p in list(pw_pending):
                    try:
                        if _scrape_slipstream(p)["prewarm_ready"]:
                            pw_pending.discard(p)
                    except Exception:
                        pass
                if pw_pending:
                    time.sleep(1.0)
            prewarm_compile_s = round(time.perf_counter() - t_pw, 3)
            if pw_pending:
                print(f"[churn-mp] WARNING: prewarm not ready on ports "
                      f"{sorted(pw_pending)} after the {warm_wait:.0f}s "
                      f"hard timeout; proceeding — early waves may pay "
                      f"cold compiles", file=sys.stderr, flush=True)
            else:
                print(f"[churn-mp] prewarm set compiled in "
                      f"{prewarm_compile_s:.1f}s across "
                      f"{len(pw_ports)} process(es)",
                      file=sys.stderr, flush=True)
        while size >= 1:
            feed(f"warm{size}", size, 100000.0, master)
            warm_total += size
            if not wait_all_bound(warm_total, timeout=warm_wait):
                raise RuntimeError(f"warmup bucket {size} did not bind")
            size //= 2

        fill_count = 0
        if args.priority_storm:
            # fill the cluster EXACTLY to capacity with storm-low pods
            # (warmup pods sit at priority 0 and are evictable too); the
            # storm then has no free capacity anywhere — every
            # high-priority pod must claim its node by eviction
            capacity = args.nodes * args.storm_fill_per_node
            fill_count = capacity - warm_total
            if fill_count < 0:
                raise RuntimeError(
                    f"cluster capacity {capacity} below warmup "
                    f"{warm_total}: raise --nodes/--storm-fill-per-node")
            if args.pods > capacity:
                raise RuntimeError(
                    f"--pods {args.pods} exceeds cluster capacity "
                    f"{capacity}: nothing to evict for the overflow")
            print(f"[churn-mp] priority-storm fill: {fill_count} "
                  f"storm-low pods -> exact capacity {capacity}",
                  file=sys.stderr, flush=True)
            if fill_count:
                feed("fill", fill_count, 100000.0, master,
                     priority_class="storm-low")
                if not wait_all_bound(warm_total + fill_count,
                                      timeout=300.0):
                    raise RuntimeError("storm fill did not bind to "
                                       "capacity")
            print("[churn-mp] cluster full; offering the high-priority "
                  "storm", file=sys.stderr, flush=True)

        try:
            waves_baseline = [_scrape_wave_raw(p)
                              for p in sched_metrics_ports]
        except Exception:
            waves_baseline = [{} for _ in sched_metrics_ports]
        # kube-slipstream: the load window opens HERE — snapshot the
        # encoder resync counters so the record can prove the invariant
        # (zero FULL re-encodes inside the window; warmup fulls are
        # expected, the encoder is born without a checkpoint)
        slip_baseline = []
        for p in sched_metrics_ports:
            try:
                slip_baseline.append(_scrape_slipstream(p))
            except Exception:
                slip_baseline.append(None)
        print(f"[churn-mp] offering {args.pods} pods at {args.rate:.0f}/s "
              f"via {args.feeders} feeder processes", file=sys.stderr,
              flush=True)
        per = args.pods // args.feeders
        counts = [per + (1 if f < args.pods % args.feeders else 0)
                  for f in range(args.feeders)]
        # pre-serialize every feeder's request stream to a replay log so
        # the paced offer loop is mmap-slice + sendall, ~0 CPU per pod
        replay_paths = [os.path.join(logdir, f"replay-{f}.bin")
                        for f in range(args.feeders)]
        storm_pc = "storm-high" if args.priority_storm else ""
        t_r = time.perf_counter()
        rthreads = [threadinglib.Thread(
            target=render_replay,
            args=(f"churn{f}", counts[f], replay_paths[f], storm_pc))
            for f in range(args.feeders)]
        for t in rthreads:
            t.start()
        for t in rthreads:
            t.join()
        render_s = time.perf_counter() - t_r
        print(f"[churn-mp] replay logs rendered in {render_s:.2f}s",
              file=sys.stderr, flush=True)

        if flight_agg is not None:
            # the offered-load window opens: the active-only SLO rules
            # (the sustained-binds floor) start judging from here
            flight_agg.set_active(True)
        if chaos_events:
            # the kill schedule's clock starts with the offered load
            run_window.set()
            threading.Thread(target=_killer, args=(time.monotonic(),),
                             daemon=True, name="chaos-killer").start()
        t0 = time.perf_counter()
        feeders = [subprocess.Popen(
            [PY, os.path.abspath(__file__), "--_feed", f"churn{f}",
             str(counts[f]), str(args.rate / args.feeders), master,
             replay_paths[f], str(args.depth), storm_pc],
            env=child_env, stdout=subprocess.PIPE, text=True)
            for f in range(args.feeders)]
        # Poll, don't block: a feeder that dies early (refused connect,
        # non-2xx storm) used to leave the run wedged inside
        # communicate() until the watchdog; now the first non-zero exit
        # aborts the run with a partial record.
        stats = [None] * args.feeders
        abort_err = None
        # scale with shape: a planet-shape feed (200k pods at a governed
        # rate) legitimately runs past the old flat 600 s ceiling; 1.5x
        # the nominal feed time + 300 s slack still catches a wedged run
        feed_deadline_s = max(600.0, args.pods / args.rate * 1.5 + 300.0)
        deadline = time.monotonic() + feed_deadline_s
        pending_f = set(range(args.feeders))
        while pending_f and abort_err is None:
            for f in list(pending_f):
                rc = feeders[f].poll()
                if rc is None:
                    continue
                pending_f.discard(f)
                out_txt = (feeders[f].communicate()[0] or "").strip()
                try:
                    stats[f] = json.loads(out_txt.splitlines()[-1])
                except (ValueError, IndexError):
                    stats[f] = {"error": f"feeder {f} exited {rc} "
                                "with no stats", "created": 0}
                if rc != 0:
                    abort_err = stats[f].get(
                        "error", f"feeder {f} exited {rc}")
            if pending_f and abort_err is None:
                if time.monotonic() > deadline:
                    abort_err = (f"feeder deadline "
                                 f"({feed_deadline_s:.0f}s) exceeded")
                    break
                time.sleep(0.2)
        feed_s = time.perf_counter() - t0
        errors = [s["error"] for s in stats
                  if isinstance(s, dict) and "error" in s]
        if abort_err or errors:
            run_window.clear()
            for f, p in enumerate(feeders):
                if p.poll() is None:
                    p.terminate()
            record = {"config": f"churn multi-process: {args.pods} pods",
                      "error": f"feeder failures: {errors or [abort_err]}",
                      "partial": True,
                      "created": sum(s.get("created", 0) for s in stats
                                     if isinstance(s, dict)),
                      "cpu_budget_s": cpu_budget()}
            # the failure runs are exactly the ones where the curves
            # matter: scrape whatever /metrics are still answering into
            # the partial record instead of writing metrics: {}, and
            # flush the flightrec timeline + alarms the same as a clean
            # run (each scrape independently best-effort — a dead
            # apiserver must not cost us the scheduler's evidence)
            try:
                record["apiserver"] = _scrape_apiserver(master)
            except Exception as e:
                record["apiserver"] = {"error": f"scrape failed: {e}"}
            try:
                ends = [_scrape_wave_raw(p) for p in sched_metrics_ports]
                per_worker = [_wave_stats_delta(b, e)
                              for b, e in zip(waves_baseline, ends)]
                record["scheduler_waves"] = per_worker[0] \
                    if len(per_worker) == 1 else {"workers": per_worker}
            except Exception as e:
                record["scheduler_waves"] = {"error": f"scrape failed: {e}"}
            if solver_addr:
                try:
                    record["solverd"] = _scrape_solverd(solverd_metrics_port)
                except Exception as e:
                    record["solverd"] = {"error": f"scrape failed: {e}"}
            try:
                record["latency"] = _scrape_pod_latency(sched_metrics_ports)
            except Exception as e:
                record["latency"] = {"error": f"scrape failed: {e}"}
            _chaos_record_sections(record)
            flush_flightrec(record)
            print(json.dumps(record, indent=1))
            if args.out:
                with open(args.out, "w") as f:
                    f.write(json.dumps(record, indent=1) + "\n")
            return 1
        if args.priority_storm:
            # bound-frame counting undercounts here (victim DELETEs shrink
            # the bound set), so storm completion is judged directly: no
            # unbound pod remains — every storm pod claimed its node
            def wait_storm_done(timeout=300.0):
                deadline = time.monotonic() + timeout
                while time.monotonic() < deadline:
                    try:
                        lst = json.loads(urllib.request.urlopen(
                            f"{master}/api/v1/pods?fieldSelector="
                            "spec.host%3D", timeout=30).read())
                        if not lst.get("items"):
                            return True
                    except Exception:
                        pass
                    time.sleep(0.25)
                return False

            ok = wait_storm_done()
        else:
            ok = wait_all_bound(warm_total + args.pods,
                                timeout=args.bound_timeout)
        run_window.clear()  # kills from here would corrupt the scrapes
        total_s = time.perf_counter() - t0
        if flight_agg is not None:
            # load window closed: active-only rules stand down (a binds
            # floor alarm after the last pod bound would be noise)
            flight_agg.set_active(False)
        offered = sum(s["created"] for s in stats) / feed_s
        sustained = args.pods / total_s if ok else 0.0
        frag = None
        if args.fragment_storm:
            # the defrag window opens AFTER the offered-load clock
            # closes: the feed left the template pods smeared across
            # every node; cordon the most-loaded nodes and wait for the
            # descheduler's waves (declining with pending_work until
            # now) to drain them and consolidate the sparse remainder
            frag = {"cordoned": args.storm_cordon}
            try:
                before = _frag_score(client, api)
                frag["score_before"] = before["score"]
                # cordon the most-resident nodes: the drain has to move
                # real pods, not tick a box on already-empty nodes
                ranked = sorted(before["resident"].items(),
                                key=lambda kv: (-kv[1], kv[0]))
                cordon = [name for name, _ in
                          ranked[:args.storm_cordon]]
                rc = client.resource("nodes", "")
                for name in cordon:
                    node = rc.get(name)
                    node.spec.unschedulable = True
                    rc.update(node)
                print(f"[churn-mp] fragment-storm: score "
                      f"{before['score']}, cordoned {len(cordon)} "
                      f"nodes, waiting on defrag waves "
                      f"(window {args.defrag_window:.0f}s)",
                      file=sys.stderr, flush=True)
                frag_deadline = time.monotonic() + args.defrag_window
                drained = False
                while time.monotonic() < frag_deadline:
                    time.sleep(2.0)
                    try:
                        mid = _scrape_defrag(desched_metrics_port)
                    except Exception:
                        continue
                    if mid["nodes_drained"] >= len(cordon):
                        drained = True
                        break
                # counters first, then truth: a wave committing between
                # the two scrapes makes the LISTed score slightly BETTER
                # than the counters claim, never worse
                frag.update(_scrape_defrag(desched_metrics_port))
                after = _frag_score(client, api)
                frag["score_after"] = after["score"]
                frag["unbound_after"] = after["unbound"]
                frag["cordoned_drained_ok"] = drained and all(
                    after["resident"].get(n, 0) == 0 for n in cordon)
            except Exception as e:
                frag["error"] = f"fragment-storm window failed: {e}"
        # per-wave encode/solve stats from the scheduler's /metrics —
        # the incremental-encoder cost under churn, measured in the live
        # topology (ref: the MapPodsToMachines rebuild being designed
        # away, pkg/scheduler/predicates.go:354-375)
        try:
            ends = [_scrape_wave_raw(p) for p in sched_metrics_ports]
            per_worker = [_wave_stats_delta(b, e)
                          for b, e in zip(waves_baseline, ends)]
            wave_stats = per_worker[0] if len(per_worker) == 1 \
                else {"workers": per_worker}
        except Exception as e:
            wave_stats = {"error": f"metrics scrape failed: {e}"}
        sched_desc = ("tpu-batch scheduler"
                      if args.schedulers == 1 else
                      f"{args.schedulers} tpu-batch scheduler workers")
        if solver_addr:
            sched_desc += " -> shared kube-solverd (wave coalescing"
            if args.mesh_devices:
                sched_desc += (f", {args.mesh_devices}-device mesh "
                               "dispatch")
            sched_desc += ")"
        if args.watchers:
            sched_desc += f" + {args.watchers} observer watch streams"
        if args.priority_storm:
            sched_desc += (" | PRIORITY STORM: cluster pre-filled to "
                           "capacity, storm binds via atomic evict+bind")
        if args.fragment_storm:
            sched_desc += (" | FRAGMENT STORM: post-feed cordon + "
                           "kube-descheduler consolidation waves "
                           "(atomic evict-here + bind-there migrations)")
        if args.chaos:
            sched_desc += (" | CHAOS: scheduled SIGKILLs + supervised "
                           "respawns mid-run"
                           + (" (kube-store on DurableStore)"
                              if args.store_data_dir else ""))
        if args.overload:
            sched_desc += (" | OVERLOAD: fairshed flow admission, "
                           f"workload backlog governor at "
                           f"{args.fairshed_backlog}, feeders riding "
                           "429 + Retry-After")
        budget = cpu_budget()
        budget["feeders"] = round(sum(s.get("cpu_s", 0.0) for s in stats), 2)
        striped = (f" ({args.store_shards}-shard stripestore)"
                   if args.store_shards > 1 else "")
        record = {
            "config": f"churn multi-process: {args.pods} pods at "
                      f"{args.rate:.0f}/s onto {args.nodes} nodes",
            "topology": (f"{args.apiservers} apiserver workers "
                         f"(SO_REUSEPORT) + kube-store{striped} + "
                         if args.apiservers > 1
                         else f"apiserver{striped} + ")
                        + sched_desc + " + "
                        f"{args.feeders} replay-log feeders, separate "
                        "processes, HTTP",
            "offered_pods_per_s": round(offered, 1),
            "sustained_pods_per_s": round(sustained, 1),
            "all_bound": ok,
            "feed_s": round(feed_s, 2),
            "total_s": round(total_s, 2),
            "wave_period_s": args.wave_period,
            "replay_render_s": round(render_s, 2),
            "feeder_behind_max_s": max(s["behind_max_s"] for s in stats),
            "scheduler_waves": wave_stats,
            # which host stage owns the core budget (utime+stime per
            # component over the whole run; feeders self-reported)
            "cpu_budget_s": budget,
            # the load generator's own cost normalized to shape: the
            # coalesced-sendall/batched-ack feed loop's efficiency claim
            # in one number (kubemark principle: the feeder must stay
            # cheap enough to never be the bottleneck it measures)
            "feeder_cpu_s_per_10k": round(
                budget["feeders"] / max(args.pods, 1) * 10_000, 3),
            "host_cores": os.cpu_count(),
        }
        # kube-slipstream evidence: encoder resync discipline inside the
        # load window (journal replay must cover every gap — FULL
        # re-encodes in-window are the O(cluster) stall this round
        # deletes), the ahead-of-time compile work, and the worst single
        # wave stall (the perfgate advisory key). in_window deltas are
        # against the scrape taken when the load window opened.
        try:
            slip_ends = [_scrape_slipstream(p)
                         for p in sched_metrics_ports]
            replay0 = sum(b["resync_replay"] for b in slip_baseline if b)
            full0 = sum(b["resync_full"] for b in slip_baseline if b)
            reasons: dict = {}
            for e in slip_ends:
                for r, v in e["resync_full_reasons"].items():
                    reasons[r] = reasons.get(r, 0) + v
            replay_end = sum(e["resync_replay"] for e in slip_ends)
            full_end = sum(e["resync_full"] for e in slip_ends)
            record["slipstream"] = {
                "prewarm_enabled": bool(args.prewarm),
                "prewarm_compile_s": prewarm_compile_s,
                "prewarm_compiles": sum(e["prewarm_compiles"]
                                        for e in slip_ends),
                "resync_replay": replay_end,
                "resync_replay_in_window": replay_end - replay0,
                "resync_full": full_end,
                "resync_full_in_window": full_end - full0,
                "resync_full_reasons": reasons,
                # running max since scheduler boot; the baseline value
                # discloses how much of it warmup owns
                "stall_max_s": round(max((e["stall_max_s"]
                                          for e in slip_ends),
                                         default=0.0), 3),
                "stall_warmup_max_s": round(max(
                    (b["stall_max_s"] for b in slip_baseline if b),
                    default=0.0), 3),
            }
            if solver_addr:
                try:
                    record["slipstream"]["solverd_prewarm_compiles"] = \
                        _scrape_slipstream(solverd_metrics_port)[
                            "prewarm_compiles"]
                except Exception:
                    pass
        except Exception as e:
            record["slipstream"] = {"error": f"scrape failed: {e}"}
        # the apiserver hot-path evidence (encode-once fan-out + batch
        # bind): scraped from the live server, plus the live per-bind
        # cost derived from the scheduler's commit-wave quantiles. A
        # reuseport fleet is scraped per-worker (identity gauges route
        # the shards) and merged into fleet-wide counters/quantiles,
        # with the per-worker disclosure rows riding alongside.
        try:
            if args.apiservers > 1:
                worker_raws = _scrape_worker_raws(master, args.apiservers)
                ap = _parse_apiserver(list(worker_raws.values()))
                ap["workers"] = _worker_disclosure(
                    worker_raws, feed_s,
                    {name: p.pid for name, p in procs})
            else:
                ap = _scrape_apiserver(master)
            ap["workers_configured"] = args.apiservers
        except Exception as e:
            ap = {"error": f"scrape failed: {e}"}
        commit = wave_stats.get("commit") if isinstance(wave_stats, dict) \
            else None
        if isinstance(commit, dict) and commit.get("waves"):
            # client-observed: commit-wave p50 over the average wave size
            # (the same derivation that put r07's wall at ~1.8 ms/bind)
            ap["per_bind_ms_live"] = round(
                commit["p50_ms"] / (args.pods / commit["waves"]), 3)
        else:
            ap.setdefault("per_bind_ms_live", 0.0)
        ap["bind_parity"] = parity
        ap["bind_probe"] = bind_probe
        if args.watchers:
            ap["observer_watchers"] = args.watchers
            ap["observer_frames"] = sum(observer_frames)
        record["apiserver"] = ap
        churn_done.set()  # monitor/observer threads stop reconnecting
        if solver_addr:
            try:
                record["solverd"] = _scrape_solverd(solverd_metrics_port)
            except Exception as e:
                record["solverd"] = {"error": f"scrape failed: {e}"}
            # supervisor evidence: 0 on a clean run; a respawned daemon
            # (native crash mid-churn) is disclosed, never hidden
            record["solverd_restarts"] = restarts.get("solverd", 0)
            # waves the schedulers solved in their own processes beside
            # the daemon. Under --platform ambient only the daemon has
            # the chip, so each of these ran on a CPU backend: the run
            # then fails rather than pass as a chip measurement
            in_process: dict = {}
            try:
                for port in sched_metrics_ports:
                    for k, n in _scrape_wave_programs(port).items():
                        in_process[k] = in_process.get(k, 0) + n
            except Exception as e:
                in_process = {"error": f"scrape failed: {e}"}
            record["scheduler_in_process_waves"] = in_process
            if args.platform == "ambient" and in_process:
                print(f"[churn-mp] FAIL: --platform ambient, yet "
                      f"schedulers solved waves in-process beside "
                      f"solverd: {in_process}", file=sys.stderr, flush=True)
                ok = False
        # pod-lifecycle latency: always scraped (the histograms are
        # metrics, on regardless of --trace) and logged as quantiles at
        # the end of every run; required in r10+ records
        try:
            latency = _scrape_pod_latency(sched_metrics_ports)
            print("[churn-mp] pod e2e scheduling p50/p95/p99 = "
                  f"{latency.get('e2e_p50_s', 0)}/"
                  f"{latency.get('e2e_p95_s', 0)}/"
                  f"{latency.get('e2e_p99_s', 0)} s over "
                  f"{latency.get('e2e_count', 0)} pods; bind->watch "
                  f"observe p50/p95 = "
                  f"{latency.get('watch_observe_p50_s', 0)}/"
                  f"{latency.get('watch_observe_p95_s', 0)} s",
                  file=sys.stderr, flush=True)
        except Exception as e:
            latency = {"error": f"latency scrape failed: {e}"}
        if args.trace:
            # drain every process's span ring and merge the shards into
            # one Perfetto-loadable artifact next to --out
            ports = list(sched_metrics_ports)
            if solver_addr:
                ports.append(solverd_metrics_port)
            shards, drain_errors, api_seen = _collect_trace_shards(
                master, ports, args.apiservers)
            latency["trace_shards"] = len(shards)
            latency["trace_spans"] = sum(
                len(s.get("spans", ())) for s in shards)
            latency["spans_dropped"] = sum(
                int(s.get("dropped", 0)) for s in shards)
            latency["trace_drain_errors"] = drain_errors
            if api_seen < args.apiservers:
                # a whole worker's shard is missing — disclose it in the
                # record; the merged trace is partial, not lossless
                latency["trace_api_workers_missed"] = \
                    args.apiservers - api_seen
                print(f"[churn-mp] WARNING: drained only {api_seen}/"
                      f"{args.apiservers} apiserver worker trace shards",
                      file=sys.stderr, flush=True)
            if args.out:
                from kubernetes_tpu.util import tracing
                trace_path = re.sub(r"\.json$", "", args.out) \
                    + "_trace.json"
                tracing.dump_chrome(shards, trace_path)
                latency["trace_file"] = os.path.basename(trace_path)
                print(f"[churn-mp] merged trace ({latency['trace_spans']} "
                      f"spans, {latency['trace_shards']} shards) -> "
                      f"{trace_path} (open at ui.perfetto.dev)",
                      file=sys.stderr, flush=True)
        else:
            latency.setdefault("trace_shards", 0)
            latency.setdefault("spans_dropped", 0)
        record["latency"] = latency
        # kube-explain + event-recorder disclosure (required r13+): a
        # clean run proves pods: 0 / reasons: {} — the layer costs
        # nothing when every pod binds; a degraded run carries the
        # why-pending histogram
        try:
            record["unschedulable"] = _scrape_unschedulable(
                sched_metrics_ports)
            un = record["unschedulable"]
            print(f"[churn-mp] unschedulable: {un['pods']} pods "
                  f"({un['reasons'] or 'none'}), "
                  f"{un['explain_invocations']} explain invocations "
                  f"({un['explain_seconds']}s), events "
                  f"{un['events_posted']} posted / "
                  f"{un['events_dropped']} dropped",
                  file=sys.stderr, flush=True)
        except Exception as e:
            record["unschedulable"] = {"error": f"scrape failed: {e}"}
        if args.overload or args.fairshed_backlog:
            # overload shape marker (perfgate isolates +overload) + the
            # kube-fairshed evidence: sheds required and DISCLOSED, the
            # system flow proven starvation-free (shed count 0), and
            # the clients' Retry-After-driven retries counted
            record["overload"] = {
                "rate_target_per_s": args.rate,
                "backlog_limit": args.fairshed_backlog,
            }
            try:
                fsec = _scrape_fairshed(master)
            except Exception as e:
                fsec = {"error": f"scrape failed: {e}"}
            if "error" not in fsec:
                fsec["retried_429"] = sum(
                    int(s.get("retried_429", 0)) for s in stats
                    if isinstance(s, dict))
                lower_shed = sum(
                    sum(d["shed"].values())
                    for f, d in fsec["flows"].items() if f != "system")
                record["overload"]["sheds_ok"] = (
                    lower_shed > 0 and fsec["system_shed"] == 0)
                print(f"[churn-mp] fairshed: {fsec['shed_total']} shed "
                      f"({lower_shed} in lower bands, system "
                      f"{fsec['system_shed']} — must be 0), "
                      f"{fsec['admitted_total']} admitted, feeders "
                      f"retried {fsec['retried_429']} 429s, backlog "
                      f"depth {fsec['backlog_depth']} "
                      f"(limit {args.fairshed_backlog})",
                      file=sys.stderr, flush=True)
                if args.overload and not record["overload"]["sheds_ok"]:
                    print("[churn-mp] WARNING: overload run but lower-"
                          "band sheds are zero (or system shed "
                          "nonzero) — the governor never engaged",
                          file=sys.stderr, flush=True)
            record["fairshed"] = fsec
        if args.lag_storm:
            # marks the record as an induced-storm shape: perfgate's
            # shape key keeps it out of the clean trajectory's baselines
            record["lag_storm"] = args.lag_storm
            record["lag_storm_resyncs_seen"] = sum(lag_resyncs_seen)
        if args.priority_storm:
            # priority-storm shape marker (perfgate isolates it) + the
            # kube-preempt evidence: every storm pod bound into a FULL
            # cluster, zero equal-or-higher evictions, preempt-to-bind
            # latency populated
            record["priority_storm"] = {
                "fill_pods": fill_count + warm_total,
                "fill_per_node": args.storm_fill_per_node,
                "storm_pods": args.pods,
            }
            try:
                record["preemption"] = _scrape_preemption(
                    sched_metrics_ports)
            except Exception as e:
                record["preemption"] = {"error": f"scrape failed: {e}"}
            pr = record["preemption"]
            if "error" not in pr:
                print(f"[churn-mp] preemption: {pr['attempts']} "
                      f"evict+bind commits, {pr['victims']} victims, "
                      f"{pr['conflicts']} conflicts, "
                      f"{pr['higher_evictions']} equal-or-higher "
                      f"evictions (must be 0); preempt-to-bind "
                      f"p50/p95 = {pr['bind_p50_s']}/{pr['bind_p95_s']} s",
                      file=sys.stderr, flush=True)
        if args.fragment_storm:
            # fragment-storm shape marker (perfgate isolates
            # +fragmentstorm) + the kube-defrag evidence assembled in
            # the post-feed window above
            record["fragmentation"] = frag
            if frag and "error" not in frag:
                print(f"[churn-mp] fragmentation: score "
                      f"{frag['score_before']} -> {frag['score_after']} "
                      f"over {frag['waves']} waves, "
                      f"{frag['migrations_committed']} migrations "
                      f"committed ({frag['migrations_409']} lost to "
                      f"commit guards), {frag['nodes_drained']} nodes "
                      f"drained / {frag['nodes_emptied']} emptied, "
                      f"cordon drained: {frag['cordoned_drained_ok']}, "
                      f"unbound after: {frag['unbound_after']} "
                      f"(must be 0)", file=sys.stderr, flush=True)
        _chaos_record_sections(record)
        flush_flightrec(record)
        missing = validate_record(record, round_no=19)
        if missing:
            print(f"[churn-mp] WARNING: record missing contract fields: "
                  f"{missing}", file=sys.stderr, flush=True)
        out = json.dumps(record, indent=1)
        print(out)
        if args.out:
            with open(args.out, "w") as f:
                f.write(out + "\n")
        return 0 if ok else 1
    finally:
        supervise_stop.set()  # the supervisor must not respawn a child
        #                       this teardown just terminated
        for _name, p in list(procs):
            p.terminate()
        if supervised:
            # sweep until quiescent: a supervisor tick in flight when
            # stop was set may append one last respawn mid-iteration
            # (and a slow Popen can land it AFTER a single fixed-delay
            # second sweep — the leak that held the solverd port against
            # the next harness run). Nothing this harness started may
            # outlive it.
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                time.sleep(0.2)
                live = [p for _n, p in procs if p.poll() is None]
                if not live:
                    break
                for p in live:
                    p.terminate()
            for _name, p in procs:
                if p.poll() is None:
                    p.kill()
        if share_seg_path:
            try:
                os.unlink(share_seg_path)
            except OSError:
                pass


if __name__ == "__main__":
    sys.exit(main())
