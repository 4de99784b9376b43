"""``sched-topology-5000n`` and its cell ``topology-5000n-backlog``: the
region/zone -> rack sample Policy of OpenShift Origin 3.x's admin guide
(region and zone service affinity, rack service anti-affinity) over the
Kubernetes scalability load test's services and pods. The committed
configuration is what ``tools/make_topology_config.py`` writes; its pods,
services, traffic, env and scheduler are ``sched-policy-5000n``'s key for
key but the node labels and the Policy, so ``policy-5000n-backlog`` is its
exact control; the control's six zones dealt over 126 racks of 40 and 39
nodes, each level's values unique; the three new per-layer metrics have
file, reader and cells, read the configuration's sizes, and find nothing or
0 on a program that keeps no such series; the cell rehearses end to end on
the CPU at 378 nodes, ``serial_policy`` deciding, every window wave on the
kernel with its node planes patched."""

import importlib
import json
import os
import subprocess
import sys

from benchmarks import roofline, roofline_policy
from benchmarks.harness import deployment as dep
from benchmarks.tools import make_policy_config as control_tool
from benchmarks.tools import make_topology_config as tool

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CONFIG, CELL = "sched-topology-5000n", "topology-5000n-backlog"
CONTROL_CONFIG, CONTROL = "sched-policy-5000n", "policy-5000n-backlog"
NEW_METRICS = ("encode_zones_ms", "wave_group_cap",
               "solve_pallas_topology_roofline")
LEVELS = ("region", "zone", "rack")
KERNEL = "%_solve_pallas_x32.1 = (s32[128]{0}, s32[128]{0}) custom-call("


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _config(name=CONFIG):
    return _load(ROOT, "benchmarks", "configs", name + ".json")


def test_the_committed_file_is_what_the_tool_writes():
    assert tool.main(["--check"]) == 0


def test_it_is_the_policy_file_but_for_the_node_labels_and_the_policy():
    mine, control = _config(), _config(CONTROL_CONFIG)
    for key in ("pod_templates", "services", "nodes", "namespace",
                "measured_pods", "init_pods", "groups", "source_sizes",
                "held_sizes", "reduced", "kernel_program", "env",
                "reference"):
        assert mine[key] == control[key], key
    assert dict(mine["scheduler"], policy=None) == \
        dict(control["scheduler"], policy=None)
    assert mine["scheduler"]["policy"] == tool.POLICY
    assert mine["reference"] == "serial_policy"
    # the policy: the control's default entries, the sample's affinity over
    # region and zone and its rack spread at weight 1
    policy = mine["scheduler"]["policy"]
    assert policy["predicates"][:4] == \
        control_tool.POLICY["predicates"][:4]
    assert policy["priorities"][:2] == \
        control_tool.POLICY["priorities"][:2]
    assert [(p["name"], p["argument"]) for p in policy["predicates"][4:]] \
        == [("RegionZoneAffinity",
             {"serviceAffinity": {"labels": ["region", "zone"]}})]
    assert [(p["name"], p["weight"], p["argument"])
            for p in policy["priorities"][2:]] == \
        [("RackSpread", 1, {"serviceAntiAffinity": {"label": "rack"}})]
    # the sample is quoted where the configuration says what it assumed
    assert tool.SAMPLE.strip().replace("\n", " / ") in \
        mine["assumed"]["policy"]
    # the guarantees keep the control's first four
    assert mine["guarantees"][:4] == control["guarantees"][:4]
    assert {"policy", "labels", "KTPU_PACK_TRANSFER"} <= set(mine["assumed"])
    # the cell: the control's traffic and chips
    bench = _load(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    assert (cells[CELL]["config"], cells[CELL]["traffic"],
            cells[CELL]["chips"]) == (CONFIG, "backlog", 1)
    assert (cells[CONTROL]["traffic"], cells[CONTROL]["chips"]) == \
        ("backlog", 1)
    [entry] = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == mine["reduced"] == \
        ["measured_pods", "init_pods", "groups"]
    assert entry["source"] == mine["source"]
    assert len(mine["source"]) <= 200 and "load.go" in mine["source"]


def test_the_nodes_are_the_control_s_zones_in_126_racks_each_value_unique():
    mine, control = _config(), _config(CONTROL_CONFIG)
    pools = dep.node_templates(mine)
    # each of the control's zones, 834 or 833 nodes, in 21 racks of 40 and
    # 39: the fewest racks of at most 40
    zones = dep.node_templates(control)
    assert [p["count"] for p in pools] == [
        n for z in zones for n in
        [40] * (z["count"] - 21 * 39) + [39] * (21 * 40 - z["count"])]
    for z in zones:
        mine_z = [p for p in pools if p["labels"]["zone"] == z["name"]]
        assert sum(p["count"] for p in mine_z) == z["count"]
        assert all({k: p["labels"][k] for k in z["labels"]} == z["labels"]
                   for p in mine_z)
    assert sum(p["count"] for p in pools) == mine["nodes"] == 5000
    assert sum(p["count"] == 40 for p in pools) == 86
    assert all(p["capacity"] == zones[0]["capacity"] for p in pools)
    assert all(set(p["labels"]) == set(LEVELS) for p in pools)
    for i, level in enumerate(LEVELS):
        values = {p["labels"][level] for p in pools}
        assert len(values) == (2, 6, 126)[i], level
        # a value names its parents: the same value is never under two
        for p in pools:
            parent = p["labels"][LEVELS[i - 1]] if i else None
            assert parent is None or p["labels"][level].startswith(
                parent + "-")
    assert len({p["name"] for p in pools}) == 126


def test_the_roofline_reads_the_configuration_s_sizes():
    config = _config()
    policy = config["scheduler"]["policy"]
    labels = [p["argument"]["serviceAffinity"]["labels"]
              for p in policy["predicates"] if "argument" in p]
    zones = [p["argument"]["serviceAntiAffinity"]["label"]
             for p in policy["priorities"] if "argument" in p]
    vocab = max(len({t["labels"][z] for t in config["node_templates"]})
                for z in zones)
    args = _load(ROOT, "benchmarks", "metrics",
                 "solve_pallas_topology_roofline.json")["args"]
    assert (args["L"], args["A"], args["V"]) == \
        (sum(map(len, labels)), len(zones), vocab) == (2, 1, 126)
    # the control's roofline keeps its own sizes
    control = _load(ROOT, "benchmarks", "metrics",
                    "solve_pallas_policy_roofline.json")["args"]
    assert (control["L"], control["A"], control["V"]) == (1, 1, 6)
    assert {k: v for k, v in args.items() if k not in "LAV"} == \
        {k: v for k, v in control.items() if k not in "LAV"}
    # one traced kernel a wave of 1 ms: a share in (0, 100)
    from benchmarks.readers import roofline_policy as reader
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [(KERNEL, 0, 1_000_000),
                                       (KERNEL, 10 ** 7, 1_000_000)]}]}]}
    waves = [{"t": 0.0, "dims": {"P": 256, "N": 5000, "R": 2}}] * 2
    after = ("scheduler_wave_groups_total 420\n"
             "scheduler_wave_solve_seconds_count 2\n")
    ctx = {"metrics_before": "", "metrics_after": after, "trace": trace,
           "traced_waves": waves, "device_kind": "TPU v5 lite"}
    share = reader.read(ctx, args)
    least, _bound = roofline_policy.least_seconds(
        {"P": 256, "N": 5000, "R": 2, "G": 210.0, "L": 2, "A": 1, "V": 126},
        roofline.peaks_for("TPU v5 lite"))
    assert abs(share - 100.0 * least / 0.001) < 1e-9
    assert 0 < share < 100


def test_the_new_metrics_have_file_reader_and_their_cells():
    bench = _load(ROOT, "BENCHMARK.json")
    entries = {m["name"]: m for m in bench["per_layer"]}
    layers = {"encode_zones_ms": "encode", "wave_group_cap": "wave loop",
              "solve_pallas_topology_roofline": "kernel"}
    for name in NEW_METRICS:
        entry = entries[name]
        doc = _load(ROOT, "benchmarks", "metrics", name + ".json")
        assert {k: doc[k] for k in entry} == entry
        assert entry["workloads"] == (
            [CELL] if "roofline" in name else [CONTROL, CELL])
        assert entry["moves"] == "pods_per_s"
        assert entry["layer"] == layers[name]
        reader = importlib.import_module(
            f"benchmarks.readers.{doc['reader']}")
        assert callable(reader.read)
    # the cell reports what its control reports, its own roofline in place
    # of the control's, whose sizes are the control's
    for m in bench["per_layer"]:
        where = m.get("workloads", [])
        if m["name"] in NEW_METRICS:
            continue
        assert (CELL in where) == (CONTROL in where and m["name"] !=
                                   "solve_pallas_policy_roofline"), m["name"]


def test_a_reader_finds_nothing_or_zero_on_the_parent_and_does_not_raise():
    """The parent keeps no zone span and no cap counter."""
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
    assert values == {"encode_zones_ms": None, "wave_group_cap": 0.0,
                      "solve_pallas_topology_roofline": None}


def test_the_cell_rehearses_at_378_nodes_on_the_incremental_path():
    """Three nodes a rack, the cell's services and pods: every window wave
    on the kernel, its node planes patched, the cap read, serial_policy
    deciding host and score over 126 rack values."""
    pools = _config()["node_templates"]
    for t in pools:
        t["count"] = 3
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 4242), "--seconds", "6",
         "--trace", "1", "--rehearse", "1", "--control", "1",
         "--config-set", "nodes=378",
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
    assert {"encode_zones_ms", "wave_group_cap", "encode_anchors_ms"} <= \
        set(res["names"])
    side = json.loads([ln for ln in proc.stderr.splitlines()
                       if ln.startswith("run.py: {")][-1][len("run.py: "):])
    assert side["compared_pods"] > 500
    assert set(side["programs"]) == {"pallas@cpu"}
    assert side["resident"]["window"] == \
        {"patched": side["window_waves"]}, side["resident"]
