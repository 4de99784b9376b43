"""``sched-policy-5000n`` and its cell ``policy-5000n-backlog``: OpenShift
Origin 3.x's default scheduler Policy (Region service affinity, Zone service
anti-affinity at weight 2) over the Kubernetes scalability load test's
services and pods. The committed configuration is what
``tools/make_policy_config.py`` writes; its pods, services, traffic and
scheduler are ``sched-load-5000n``'s key for key but the node labels and the
Policy, so ``load-5000n-backlog`` is its exact control; the roofline count
on a hand-sized wave; the three new per-layer metrics have file, reader and
cell, and find nothing on a program that keeps no such series; the cell
rehearses end to end on the CPU at 480 nodes, ``serial_policy`` deciding,
every window wave on the kernel with its node planes patched."""

import importlib
import json
import os
import subprocess
import sys

from benchmarks import roofline_groups, roofline_policy
from benchmarks.harness import deployment as dep
from benchmarks.tools import make_policy_config as tool

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CONFIG, CELL = "sched-policy-5000n", "policy-5000n-backlog"
CONTROL_CONFIG, CONTROL = "sched-load-5000n", "load-5000n-backlog"
NEW_METRICS = ("encode_anchors_ms", "anchored_pods_share",
               "solve_pallas_policy_roofline")
KERNEL = "%_solve_pallas_x32.1 = (s32[128]{0}, s32[128]{0}) custom-call("


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _config(name=CONFIG):
    return _load(ROOT, "benchmarks", "configs", name + ".json")


def test_the_committed_file_is_what_the_tool_writes():
    assert tool.main(["--check"]) == 0


def test_it_is_the_load_file_but_for_the_node_labels_and_the_policy():
    mine, control = _config(), _config(CONTROL_CONFIG)
    for key in ("pod_templates", "services", "nodes", "namespace",
                "measured_pods", "init_pods", "groups", "source_sizes",
                "held_sizes", "reduced", "kernel_program"):
        assert mine[key] == control[key], key
    assert dict(mine["scheduler"], policy=None) == \
        dict(control["scheduler"], policy=None)
    assert mine["scheduler"]["policy"] == tool.POLICY
    assert mine["env"] == dict(control["env"], KTPU_PACK_TRANSFER="off")
    assert "KTPU_PACK_TRANSFER" in mine["assumed"]
    # the same 5,000 nodes of 4 cpu / 32Gi, labelled by region and zone
    pools, [c_pool] = dep.node_templates(mine), dep.node_templates(control)
    assert sum(p["count"] for p in pools) == c_pool["count"] == 5000
    assert all(p["capacity"] == c_pool["capacity"] for p in pools)
    assert sorted((p["labels"]["region"], p["labels"]["zone"])
                  for p in pools) == [(r, f"{r}-{z}") for r in ("r1", "r2")
                                      for z in "abc"]
    # the fixture the Policy was first run from, as its review accepted it
    fixture = _load(HERE, "fixtures", "policy-load-5000n.json")
    assert mine["node_templates"] == fixture["node_templates"]
    assert mine["scheduler"] == fixture["scheduler"]
    assert mine["reference"] == fixture["reference"] == "serial_policy"
    # the cell: the control's traffic and chips
    bench = _load(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    assert (cells[CELL]["traffic"], cells[CELL]["chips"]) == \
        (cells[CONTROL]["traffic"], cells[CONTROL]["chips"]) == ("backlog", 1)
    [entry] = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == mine["reduced"] == \
        ["measured_pods", "init_pods", "groups"]
    assert len(mine["source"]) <= 200 and "load.go" in mine["source"]


def test_the_policy_roofline_on_a_hand_computed_wave():
    # P = 4 pods, N = 10 nodes, R = 2, G = 3 groups, L = 1, A = 1, V = 6
    # roofline_groups.solve_work: 1080 ops, 924 bytes
    # planes: 10*1*4 + 1*10*4 + 1*3*6*4 + 3*1*4 = 40 + 40 + 72 + 12 = 164
    # steps: 4 * (4 + 24 + 4) = 128                        => 1216 bytes
    # ops: + 4 * 10 * (3 + 7) = 400                        => 1480 ops
    dims = {"P": 4, "N": 10, "R": 2, "G": 3, "L": 1, "A": 1, "V": 6}
    assert roofline_groups.solve_work(dims) == (1080, 924)
    assert roofline_policy.solve_work(dims) == (1480, 1216)
    doc = _load(ROOT, "benchmarks", "metrics",
                "solve_pallas_policy_roofline.json")
    from benchmarks import roofline
    from benchmarks.readers import roofline_policy as reader
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [(KERNEL, 0, 1_000_000),
                                       (KERNEL, 10 ** 7, 1_000_000)]}]}]}
    waves = [{"t": 0.0, "dims": {"P": 256, "N": 5000, "R": 2}}] * 2
    after = ("scheduler_wave_groups_total 420\n"
             "scheduler_wave_solve_seconds_count 2\n")
    ctx = {"metrics_before": "", "metrics_after": after, "trace": trace,
           "traced_waves": waves, "device_kind": "TPU v5 lite"}
    share = reader.read(ctx, doc["args"])
    least, _bound = roofline_policy.least_seconds(
        {"P": 256, "N": 5000, "R": 2, "G": 210.0, "L": 1, "A": 1, "V": 6},
        roofline.peaks_for("TPU v5 lite"))
    assert abs(share - 100.0 * least / 0.001) < 1e-9
    assert 0 < share < 100
    assert ctx["notes"]["roofline_policy"]["groups_a_wave"] == 210.0


def test_the_new_metrics_have_file_reader_and_the_cell():
    bench = _load(ROOT, "BENCHMARK.json")
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        entry = entries[name]
        doc = _load(ROOT, "benchmarks", "metrics", name + ".json")
        assert {k: doc[k] for k in entry} == entry
        assert entry["workloads"] == [CELL] and entry["moves"] == "pods_per_s"
        assert entry["layer"] == ("kernel" if "roofline" in name
                                  else "encode")
        reader = importlib.import_module(
            f"benchmarks.readers.{doc['reader']}")
        assert callable(reader.read)
    # the cell reports what its control reports, the new roofline in place
    # of the count that knows no policy plane
    for m in bench["per_layer"]:
        where = m.get("workloads", [])
        if m["name"] in NEW_METRICS:
            continue
        assert (CELL in where) == (CONTROL in where and m["name"] !=
                                   "solve_pallas_groups_roofline"), m["name"]
    # the roofline's policy sizes are the configuration's
    config = _config()
    policy = config["scheduler"]["policy"]
    args = _load(ROOT, "benchmarks", "metrics",
                 "solve_pallas_policy_roofline.json")["args"]
    labels = [p["argument"]["serviceAffinity"]["labels"]
              for p in policy["predicates"] if "argument" in p]
    zones = [p["argument"]["serviceAntiAffinity"]["label"]
             for p in policy["priorities"] if "argument" in p]
    values = {t["labels"][zones[0]] for t in config["node_templates"]}
    assert (args["L"], args["A"], args["V"]) == \
        (sum(map(len, labels)), len(zones), len(values)) == (1, 1, 6)


def test_a_reader_finds_nothing_or_zero_on_the_parent_and_does_not_raise():
    """The parent keeps no anchor span and no anchor counter."""
    parent = ("scheduler_wave_pods_total 10\n"
              "scheduler_wave_solve_seconds_count 2\n")
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [(KERNEL, 0, 400_000)]}]}]}
    waves = [{"t": 0.0, "dims": {"P": 256, "N": 5000, "R": 2}}]
    ctx = {"metrics_before": "", "metrics_after": parent, "trace": trace,
           "traced_waves": waves, "device_kind": "TPU v5 lite"}
    values = {}
    for name in NEW_METRICS:
        doc = _load(ROOT, "benchmarks", "metrics", name + ".json")
        reader = importlib.import_module(
            f"benchmarks.readers.{doc['reader']}")
        values[name] = reader.read(ctx, doc["args"])
    assert values == {"encode_anchors_ms": None, "anchored_pods_share": 0.0,
                      "solve_pallas_policy_roofline": None}


def test_the_cell_rehearses_at_480_nodes_on_the_incremental_path():
    """Eighty nodes a zone, the cell's services and pods: every window wave on
    the kernel, its node planes patched, anchors read, serial_policy
    deciding host and score."""
    pools = _config()["node_templates"]
    for t in pools:
        t["count"] = 80
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 4242), "--seconds", "6",
         "--trace", "1", "--rehearse", "1", "--control", "1",
         "--config-set", "nodes=480",
         "--config-set", f"node_templates={json.dumps(pools)}",
         "--traffic-set", "warm_rounds=[1, 2, 4, 8, 16, 32, 64, 128]"],
        capture_output=True, text=True, cwd=ROOT, timeout=1500)
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert proc.returncode == 0 and lines, proc.stderr[-3000:]
    res = json.loads(lines[-1])
    compared = res["compared"]
    control = compared.pop("control.decisions_differ")
    assert res["correct"] is True, compared
    assert all(v["value"] == 0 for v in compared.values())
    assert control["value"] > 20 and res["failed"] == 0
    assert {"encode_anchors_ms", "anchored_pods_share"} <= set(res["names"])
    side = json.loads([ln for ln in proc.stderr.splitlines()
                       if ln.startswith("run.py: {")][-1][len("run.py: "):])
    assert side["compared_pods"] > 500
    assert set(side["programs"]) == {"pallas@cpu"}
    assert side["resident"]["window"] == \
        {"patched": side["window_waves"]}, side["resident"]
