"""bench.py emission contract: the final stdout line parses with
json.loads and stays < 1.5 KB regardless of how much detail the run
produced (BENCH_r05.json had parsed:null because one giant line with
inline runs_s arrays truncated in capture); the full record goes to the
detail sidecar. Every record names the device it ran on, and a run that
finds no TPU (and was not given --smoke) prints an error record.

Also the CHURN_MP_* record schema (hack/churn_mp.py validate_record):
committed churn records must carry the delta-wire evidence (hit rate,
bytes shipped vs saved) and the per-stage CPU budget, so a future round
can't silently drop the fields the acceptance gates read."""

import glob
import importlib.util
import json
import os

import bench

_LIMIT = 1500

_REPO = os.path.dirname(os.path.abspath(bench.__file__))


def _load_churn_mp():
    spec = importlib.util.spec_from_file_location(
        "churn_mp", os.path.join(_REPO, "hack", "churn_mp.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fat_record():
    cfgs = {}
    for tag in ("north_star", "basic", "affinity", "binpack3", "gang",
                "churn"):
        cfgs[tag] = {
            "pods": 10_000, "nodes": 5_000, "value": 48867.1,
            "unit": "pods/s", "wave_s": 0.2046, "wave_s_p50": 0.2046,
            "wave_s_p95": 0.2397, "wave_s_p99": 0.2541,
            "runs": 30, "runs_s": [round(0.2 + i * 1e-4, 4)
                                   for i in range(30)],
            "path": "device", "encode_s": 0.0754, "device_s": 0.1293,
            "gate": "slice-oracle-600x5000",
            "serial_oracle_pods_per_s": 33.1,
            "router_host_s": 1.43, "router_device_s": 0.13,
            "router_cal_s": 21.4, "router_cold_s": 4.61,
        }
    return {
        "metric": "pods_scheduled_per_sec_10000pods_5000nodes",
        "value": 75028.5, "unit": "pods/s", "vs_baseline": 7.503,
        "timing": bench.TIMING_DESC,
        "configs": cfgs,
    }


def test_compact_line_parses_and_fits():
    line = bench._compact_record(_fat_record(), detail_name="X_detail.json")
    assert len(line) < _LIMIT, len(line)
    rec = json.loads(line)
    assert rec["metric"].startswith("pods_scheduled_per_sec")
    assert rec["value"] == 75028.5
    assert rec["detail"] == "X_detail.json"
    assert "runs_s" not in json.dumps(rec)   # arrays live in detail only
    assert rec["configs"]["north_star"]["value"] == 48867.1


def test_compact_line_degrades_under_pressure_but_keeps_values():
    rec = _fat_record()
    # 40 configs cannot all fit with every optional key — the compactor
    # must shed keys (and at the limit, whole configs) before the budget
    rec["configs"] = {f"cfg_{i:02d}": dict(rec["configs"]["north_star"])
                      for i in range(40)}
    line = bench._compact_record(rec)
    assert len(line) < _LIMIT, len(line)
    out = json.loads(line)
    assert out["value"] == 75028.5


def test_compact_is_idempotent_on_already_compact_records():
    line1 = bench._compact_record(_fat_record())
    line2 = bench._compact_record(json.loads(line1))
    rec1, rec2 = json.loads(line1), json.loads(line2)
    assert rec2["configs"]["north_star"].get("p50") == \
        rec1["configs"]["north_star"].get("p50")
    assert len(line2) < _LIMIT


def _churn_sample_record():
    return {
        "config": "churn multi-process: 50000 pods at 1000/s onto "
                  "10000 nodes",
        "topology": "4 apiserver workers + kube-store + 2 tpu-batch "
                    "scheduler workers -> shared kube-solverd + 4 "
                    "replay-log feeders",
        "offered_pods_per_s": 1001.2, "sustained_pods_per_s": 1000.3,
        "all_bound": True, "feed_s": 49.9, "total_s": 50.0,
        "replay_render_s": 1.2, "feeder_behind_max_s": 0.05,
        "scheduler_waves": {"encode": {"waves": 50, "mean_ms": 5.0,
                                       "p50_ms": 4.0, "p95_ms": 9.0}},
        "cpu_budget_s": {"apiserver": 40.1, "scheduler": 30.2,
                         "solverd": 25.3, "feeders": 2.0},
        "host_cores": 24,
        "solverd": {"device_solves": 50, "waves_served": 55,
                    "coalesce_factor": 1.1,
                    "delta_hits": 48, "delta_full_frames": 2,
                    "delta_resyncs": 0, "delta_hit_rate": 0.96,
                    "delta_bytes_shipped": 10_000_000,
                    "delta_bytes_saved": 200_000_000},
        "apiserver": {"frame_cache_hits": 900_000,
                      "frame_cache_misses": 50_000,
                      "frame_cache_hit_rate": 0.947, "frame_seeds": 99_000,
                      "watch_lag_drops": 0, "watch_events_coalesced": 0,
                      "watch_events_dropped": 0,
                      "fanout_seconds": 12.5, "fanout_writes": 40_000,
                      "frames_per_write": 9.1,
                      "batch_bind_requests": 50,
                      "batch_bind_bindings": 50_000,
                      "batch_bind_p50_ms": 310.0, "batch_bind_p95_ms": 700.0,
                      "bind_server_ms_per_pod": 0.41,
                      "per_bind_ms_live": 0.8,
                      "bind_parity": {"checked": 130, "divergent": 0,
                                      "conflict_parity": True},
                      "bind_probe": {"batch_ms_per_pod": 0.4,
                                     "per_pod_ms": 1.2, "pods": 1280}},
    }


def test_churn_record_schema_accepts_complete_record():
    churn_mp = _load_churn_mp()
    assert churn_mp.validate_record(_churn_sample_record()) == []


def test_churn_record_schema_flags_dropped_fields():
    churn_mp = _load_churn_mp()
    rec = _churn_sample_record()
    del rec["cpu_budget_s"]
    del rec["solverd"]["delta_hit_rate"]
    del rec["apiserver"]["frame_cache_hit_rate"]
    del rec["apiserver"]["bind_parity"]
    missing = churn_mp.validate_record(rec)
    assert "cpu_budget_s" in missing
    assert "solverd.delta_hit_rate" in missing
    assert "apiserver.frame_cache_hit_rate" in missing
    assert "apiserver.bind_parity" in missing
    # an aborted run's partial record is exempt beyond its error marker
    assert churn_mp.validate_record(
        {"error": "feeder failures", "created": 10}) == []


def test_churn_record_schema_apiserver_fields_gated_by_round():
    """r07 records predate the apiserver hot-path family; r08+ must
    carry it (the frame-cache/batch-bind evidence the acceptance gates
    read)."""
    churn_mp = _load_churn_mp()
    rec = _churn_sample_record()
    del rec["apiserver"]
    assert churn_mp.validate_record(rec, round_no=7) == []
    assert "apiserver" in churn_mp.validate_record(rec, round_no=8)


def test_churn_record_schema_mesh_section_gated_by_round():
    """r08 records predate the mesh-sharded solve; r09+ must carry the
    solverd.mesh section (device count, pods_axis, mesh-vs-single solve
    p50, reshard bytes, parity) whenever the run had a daemon."""
    churn_mp = _load_churn_mp()
    rec = _churn_sample_record()
    assert churn_mp.validate_record(rec, round_no=8) == []
    missing = churn_mp.validate_record(rec, round_no=9)
    assert "solverd.mesh" in missing
    rec["solverd"]["mesh"] = {
        "devices": 8, "pods_axis": 1, "node_shards": 8, "waves": 50,
        "transfer_bytes": 1_000_000, "reshard_bytes": 0,
        "resident_bytes": 90_000_000, "shard_bytes_per_device": 12_000_000,
        "solve_p50_ms": 700.0, "single_device_p50_ms": 1600.0,
        "solve_waves": 50, "single_device_probes": 1,
        "parity_checks": 1, "parity_divergent": 0,
    }
    assert churn_mp.validate_record(rec, round_no=9) == []
    del rec["solverd"]["mesh"]["reshard_bytes"]
    del rec["solverd"]["mesh"]["parity_divergent"]
    missing = churn_mp.validate_record(rec, round_no=9)
    assert "solverd.mesh.reshard_bytes" in missing
    assert "solverd.mesh.parity_divergent" in missing


def test_churn_record_schema_latency_section_gated_by_round():
    """r09 records predate kube-trace; r10+ must carry the latency
    section (per-pod e2e quantiles, bind->watch-observe leg, and the
    trace-collection health counters) so the causal per-pod evidence —
    and the proof the instrument itself wasn't lossy — can't be
    silently dropped."""
    churn_mp = _load_churn_mp()
    rec = _churn_sample_record()
    rec["solverd"]["mesh"] = {k: 1 for k in churn_mp.SOLVERD_MESH_FIELDS}
    assert churn_mp.validate_record(rec, round_no=9) == []
    assert "latency" in churn_mp.validate_record(rec, round_no=10)
    rec["latency"] = {
        "e2e_count": 50_000, "e2e_mean_s": 0.8, "e2e_p50_s": 0.6,
        "e2e_p95_s": 2.1, "e2e_p99_s": 4.2,
        "watch_observe_count": 50_000, "watch_observe_mean_s": 0.07,
        "watch_observe_p50_s": 0.05, "watch_observe_p95_s": 0.2,
        "watch_observe_p99_s": 0.4,
        "trace_shards": 12, "trace_spans": 30_000, "spans_dropped": 0,
        "trace_file": "CHURN_MP_r10_fullshape_trace.json",
    }
    assert churn_mp.validate_record(rec, round_no=10) == []
    del rec["latency"]["e2e_p99_s"]
    del rec["latency"]["spans_dropped"]
    missing = churn_mp.validate_record(rec, round_no=10)
    assert "latency.e2e_p99_s" in missing
    assert "latency.spans_dropped" in missing


def test_churn_record_schema_timeline_section_gated_by_round():
    """r10 records predate kube-flightrec; r11+ must carry the timeline
    section (>= 5 headline series) and the SLO alarm transition log, so
    the continuous-series evidence — and the proof the clean run fired
    zero alarms — can't be silently dropped."""
    churn_mp = _load_churn_mp()
    rec = _churn_sample_record()
    rec["solverd"]["mesh"] = {k: 1 for k in churn_mp.SOLVERD_MESH_FIELDS}
    rec["latency"] = {k: 1 for k in churn_mp.LATENCY_FIELDS}
    assert churn_mp.validate_record(rec, round_no=10) == []
    missing = churn_mp.validate_record(rec, round_no=11)
    assert "timeline" in missing and "alarms" in missing
    rec["timeline"] = {
        "sample_period_s": 1.0, "poll_period_s": 2.0, "t0_ns": 123,
        "pids": 4, "poll_errors": 0, "workers_missed": 0,
        "series": {f"slo:rule{i}": [[0.0, 1.0], [2.0, 1.5]]
                   for i in range(6)},
        "headline": [f"slo:rule{i}" for i in range(6)],
    }
    rec["alarms"] = []
    assert churn_mp.validate_record(rec, round_no=11) == []
    # fewer than the contract's 5 headline series is non-conformant
    rec["timeline"]["series"] = {"slo:rule0": [[0.0, 1.0]]}
    missing = churn_mp.validate_record(rec, round_no=11)
    assert any(m.startswith("timeline.series:") for m in missing)
    rec["timeline"]["series"] = {f"slo:rule{i}": [[0.0, 1.0]]
                                 for i in range(6)}
    del rec["timeline"]["headline"]
    assert "timeline.headline" in churn_mp.validate_record(rec,
                                                           round_no=11)
    rec["timeline"]["headline"] = list(rec["timeline"]["series"])
    # alarms must be a LIST (a clean run records []; a dict or absence
    # would let "zero alarms" be claimed without the log)
    rec["alarms"] = {}
    assert "alarms" in churn_mp.validate_record(rec, round_no=11)


def test_churn_record_schema_unschedulable_section_gated_by_round():
    """r12 records predate kube-explain; r13+ must carry the
    unschedulable section (reason histogram, explain cost, and the
    async-event-recorder posted/dropped disclosure) — a clean run
    proves pods: 0 instead of omitting the evidence."""
    churn_mp = _load_churn_mp()
    rec = _churn_sample_record()
    rec["solverd"]["mesh"] = {k: 1 for k in churn_mp.SOLVERD_MESH_FIELDS}
    rec["latency"] = {k: 1 for k in churn_mp.LATENCY_FIELDS}
    rec["timeline"] = {"sample_period_s": 1.0,
                       "series": {f"slo:rule{i}": [[0.0, 1.0]]
                                  for i in range(6)},
                       "headline": [f"slo:rule{i}" for i in range(6)]}
    rec["alarms"] = []
    assert churn_mp.validate_record(rec, round_no=12) == []
    assert "unschedulable" in churn_mp.validate_record(rec, round_no=13)
    rec["unschedulable"] = {
        "pods": 0, "reasons": {}, "explain_invocations": 0,
        "explain_seconds": 0.0, "explain_skipped": 0,
        "events_posted": 50_000, "events_dropped": 0,
    }
    assert churn_mp.validate_record(rec, round_no=13) == []
    del rec["unschedulable"]["reasons"]
    del rec["unschedulable"]["events_dropped"]
    missing = churn_mp.validate_record(rec, round_no=13)
    assert "unschedulable.reasons" in missing
    assert "unschedulable.events_dropped" in missing


def _r16_complete_record(churn_mp):
    rec = _churn_sample_record()
    rec["solverd"]["mesh"] = {k: 1 for k in churn_mp.SOLVERD_MESH_FIELDS}
    rec["latency"] = {k: 1 for k in churn_mp.LATENCY_FIELDS}
    rec["timeline"] = {"sample_period_s": 1.0,
                       "series": {f"slo:rule{i}": [[0.0, 1.0]]
                                  for i in range(6)},
                       "headline": [f"slo:rule{i}" for i in range(6)]}
    rec["alarms"] = []
    rec["unschedulable"] = {k: 0 for k in churn_mp.UNSCHEDULABLE_FIELDS}
    return rec


def test_churn_record_schema_horizon_sections_gated_by_round():
    """r16 records predate kube-horizon; r17+ must disclose the
    apiserver worker topology (workers_configured, and a full per-worker
    row set when > 1 — a missed scrape shard is non-conformance, not
    silence) and the active sub-mesh evidence under solverd.mesh
    (compaction split + live parity probe; a divergent probe is a
    contract violation)."""
    churn_mp = _load_churn_mp()
    rec = _r16_complete_record(churn_mp)
    assert churn_mp.validate_record(rec, round_no=16) == []
    missing = churn_mp.validate_record(rec, round_no=17)
    assert "apiserver.workers_configured" in missing
    assert "solverd.mesh.submesh" in missing
    rec["apiserver"]["workers_configured"] = 1
    rec["solverd"]["mesh"]["submesh"] = {
        "waves": 40, "full_waves": 10, "nodes_kept": 80_000,
        "nodes_total": 400_000, "kept_fraction": 0.2,
        "compact_p50_ms": 5.0, "parity_checks": 1, "parity_divergent": 0,
    }
    assert churn_mp.validate_record(rec, round_no=17) == []
    # a single-worker record needs no per-worker rows; a fleet does
    rec["apiserver"]["workers_configured"] = 4
    assert "apiserver.workers" in churn_mp.validate_record(rec,
                                                           round_no=17)
    rows = [{k: i for k in churn_mp.APISERVER_WORKER_FIELDS}
            for i in range(4)]
    rec["apiserver"]["workers"] = rows
    assert churn_mp.validate_record(rec, round_no=17) == []
    rec["apiserver"]["workers"] = rows[:3]
    assert "apiserver.workers:3<4" in churn_mp.validate_record(
        rec, round_no=17)
    rec["apiserver"]["workers"] = rows
    del rows[2]["cache_seed_ring_drops"]
    assert "apiserver.workers[2].cache_seed_ring_drops" in \
        churn_mp.validate_record(rec, round_no=17)
    rows[2]["cache_seed_ring_drops"] = 0
    # the compaction's bit-identity claim is live evidence: a divergent
    # parity probe makes the whole record non-conformant
    rec["solverd"]["mesh"]["submesh"]["parity_divergent"] = 1
    assert "solverd.mesh.submesh.parity_divergent:nonzero" in \
        churn_mp.validate_record(rec, round_no=17)


def test_committed_churn_records_conform():
    """Every committed CHURN_MP record from r07 on must satisfy the
    schema (r08+ additionally the apiserver hot-path fields) — the
    contract that keeps the evidence the acceptance gates read in every
    future round's record."""
    churn_mp = _load_churn_mp()
    for path in glob.glob(os.path.join(_REPO, "CHURN_MP_r*.json")):
        if path.endswith(("_trace.json", "_timeline.json")):
            continue  # kube-trace / flightrec sidecars, not churn records
        round_no = int(path.rsplit("_r", 1)[1].split("_")[0].split(".")[0])
        if round_no < 7:
            continue  # pre-contract records are historical evidence
        with open(path) as fh:
            rec = json.load(fh)
        assert churn_mp.validate_record(rec, round_no=round_no) == [], path


def test_no_chip_and_no_smoke_fails_with_an_error_record(capsys):
    """A measurement path that finds no TPU fails: an error record that
    names the device JAX reported, exit 1, and never a committed record
    printed as its own."""
    assert bench.main(["--configs", "basic"]) == 1
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 1 and len(lines[0]) < _LIMIT
    rec = json.loads(lines[0])
    assert "no TPU" in rec["error"] and rec["value"] == 0.0
    assert rec["platform"] == "cpu" and rec["device_count"] >= 1
    assert "replayed_from" not in rec and "configs" not in rec


def test_compact_line_keeps_the_device_stamp():
    rec = dict(_fat_record(), platform="tpu", device_kind="TPU v5 lite",
               device_count=1)
    out = json.loads(bench._compact_record(rec))
    assert (out["platform"], out["device_kind"], out["device_count"]) == \
        ("tpu", "TPU v5 lite", 1)


def test_platform_ambient_gives_the_chip_to_exactly_one_child(monkeypatch):
    """hack/churn_mp.py --platform ambient: a chip belongs to one process,
    so one child inherits the ambient environment and all others are
    pinned to the CPU backend."""
    import pytest

    churn_mp = _load_churn_mp()
    ambient = {k: v for k, v in churn_mp.ENV.items() if k != "JAX_PLATFORMS"}
    monkeypatch.setattr(churn_mp, "ENV", ambient)
    children = ["storeserver", "apiserver0", "apiserver1", "solverd",
                "scheduler0", "scheduler1", "descheduler"]

    def off_cpu(owner):
        return [c for c in children
                if churn_mp.child_env_for(c, owner).get("JAX_PLATFORMS")
                != "cpu"]

    assert off_cpu(churn_mp.chip_child("cpu", True, 2)) == []
    assert off_cpu(churn_mp.chip_child("ambient", True, 2)) == ["solverd"]
    assert off_cpu(churn_mp.chip_child("ambient", False, 1)) == ["scheduler0"]
    with pytest.raises(ValueError, match="needs --solverd"):
        churn_mp.chip_child("ambient", False, 2)
