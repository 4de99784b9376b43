"""``sched-load-5000n`` and its cell ``load-5000n-backlog`` (PR 36; the
Kubernetes scalability load test's mix): the committed configuration is what
``tools/make_load_config.py`` writes from the source's constants; its counts,
split, namespaces and selectors are the source's; what the feeder posts and
the control plane registers for it is what PR 36's tree posted
(``goldens/sched-load-5000n.json``); the cell rehearses end to end on the CPU
at 500 nodes, ``serial_default`` deciding; and its four per-layer metrics
have file, reader and cells."""

import collections
import hashlib
import importlib
import json
import os
import subprocess
import sys

from benchmarks import feeder, roofline, roofline_groups
from benchmarks.harness import control_plane as cpl
from benchmarks.harness import deployment as dep
from benchmarks.tools import make_load_config as tool

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CONFIG, CELL, CONTROL = ("sched-load-5000n", "load-5000n-backlog",
                         "basic-5000n-backlog")
NEW_METRICS = {"encode_groups_ms": [CONTROL, CELL],
               "wave_groups": [CONTROL, CELL],
               "spread_peered_pods_share": [CONTROL, CELL],
               "solve_pallas_groups_roofline": [CELL]}
KERNEL = "%_solve_pallas_x32.1 = (s32[128]{0}, s32[128]{0}) custom-call("


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _config():
    return _load(ROOT, "benchmarks", "configs", CONFIG + ".json")


def _sha(lines) -> str:
    return hashlib.sha256("\n".join(lines).encode()).hexdigest()


def _encoder():
    from kubernetes_tpu.client.http import HTTPTransport
    t = HTTPTransport("http://127.0.0.1:1")
    return lambda obj: t.scheme.encode(obj, t.version)


def posted(config: dict, seed: int) -> dict:
    """What a run of this configuration with this seed posts: the window
    plan's first forty pods, the services and the nodes (the long lists by
    their first entries and a digest)."""
    encode = _encoder()
    templates = dep.pod_templates(config)
    plan = dep.pod_plan(templates, "window", seed, 40)
    factory = feeder.PodFactory(templates, {"window": plan}, seed)
    pods = [encode(factory.make()[1]) for _ in range(40)]
    services = [encode(s) for s in cpl.make_services(config)]
    nodes = [encode(n) for n in cpl.make_nodes(config, seed)]
    return {"seed": seed,
            "plan_first": [templates[i]["name"] for i in plan[:40]],
            "plan_len": len(plan), "pods_first": pods[:4],
            "pods_sha256": _sha(pods),
            "services": len(services), "services_first": services[:3],
            "services_sha256": _sha(services),
            "nodes": len(nodes), "nodes_first": nodes[:2],
            "nodes_sha256": _sha(nodes)}


def test_the_committed_file_is_what_the_tool_writes():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           CONFIG + ".json")) as f:
        assert f.read() == tool.render(tool.build())
    assert tool.main(["--check"]) == 0


def test_counts_split_namespaces_and_selectors_are_the_source_s():
    config = _config()
    published, held = tool.groups(), tool.groups(tool.HELD_PERCENT)
    by_class = collections.Counter(g[0] for g in published)
    assert by_class == {"small": 15000, "medium": 1250, "big": 150}
    assert sum(size for _, _, size in published) == 150_000
    for cls, share in (("small", 2), ("medium", 4), ("big", 4)):
        assert sum(s for c, _, s in published if c == cls) == 150_000 // share
        assert sum(s for c, _, s in held if c == cls) == 36_000 // share
    assert collections.Counter(g[0] for g in held) == \
        {"small": 3600, "medium": 300, "big": 36}
    templates, services = dep.pod_templates(config), dep.services(config)
    assert len(templates) == len(services) == len(held) == 3936
    assert sum(t["weight"] for t in templates) == 36_000
    spaces = collections.Counter(t["namespace"] for t in templates)
    assert sorted(spaces) == [f"load-{i:02d}" for i in range(50)]
    assert max(spaces.values()) - min(spaces.values()) <= 3   # round-robin
    for t, s in zip(templates, services):
        assert s == {"name": t["name"], "namespace": t["namespace"],
                     "selector": t["labels"]}
        assert t["labels"] == {"name": t["name"]}
        assert t["limits"] == {"cpu": "10m", "memory": "26214400"}
    assert config["source_sizes"]["services"] == 16_400
    assert config["source_sizes"]["nodes_per_namespace"] == 100
    assert config["reduced"] == ["measured_pods", "init_pods", "groups"]
    entry = [c for c in _load(ROOT, "BENCHMARK.json")["configs"]
             if c["name"] == CONFIG][0]
    assert entry["source"] == config["source"] and "load.go" in \
        entry["source"] and "30 pods per node" in entry["source"]
    assert entry["reduced"] == config["reduced"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"


def test_the_configuration_posts_what_pr_36_posted():
    golden = _load(HERE, "goldens", CONFIG + ".json")
    assert posted(_config(), golden["seed"]) == golden
    pod = json.loads(golden["pods_first"][0])
    name = golden["plan_first"][0]
    assert pod["metadata"]["labels"] == {"name": name}
    assert pod["metadata"]["namespace"].startswith("load-")
    limits = pod["spec"]["containers"][0]["resources"]["limits"]
    assert limits == {"cpu": "10m", "memory": "26214400"}
    assert "nodeSelector" not in pod["spec"]
    service = json.loads(golden["services_first"][0])
    assert service["spec"]["selector"] == {"name": service["metadata"]["name"]}
    assert golden["services"] == 3936 and golden["nodes"] == 5000
    # the nodes are the control's
    basic = _load(ROOT, "benchmarks", "configs", "sched-basic-5000n.json")
    encode = _encoder()
    assert _sha([encode(n) for n in cpl.make_nodes(
        basic, golden["seed"])]) == golden["nodes_sha256"]


def test_the_new_metrics_have_file_reader_and_cells():
    bench = _load(ROOT, "BENCHMARK.json")
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name, cells in NEW_METRICS.items():
        entry = entries[name]
        doc = _load(ROOT, "benchmarks", "metrics", name + ".json")
        assert {k: doc[k] for k in entry} == entry
        assert entry["workloads"] == cells
        assert entry["moves"] == "pods_per_s"
        assert entry["layer"] == ("kernel" if "roofline" in name
                                  else "encode")
        reader = importlib.import_module(
            f"benchmarks.readers.{doc['reader']}")
        assert callable(reader.read)
    [cell] = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "backlog", 1)
    # the cell reports whatever mixed-5000n-backlog reports, but the
    # roofline share whose count knows no group plane
    for m in bench["per_layer"]:
        if "mixed-5000n-backlog" in m.get("workloads", []):
            assert (CELL in m["workloads"]) == \
                (m["name"] != "solve_pallas_roofline"), m["name"]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def _ctx(after: str, trace=None, waves=()):
    return {"metrics_before": "", "metrics_after": after, "trace": trace,
            "traced_waves": list(waves), "device_kind": "TPU v5 lite"}


def test_a_reader_finds_nothing_or_zero_on_the_parent_and_does_not_raise():
    """The parent keeps no such span and no such counters."""
    parent = ("scheduler_wave_pods_total 10\n"
              "scheduler_wave_solve_seconds_count 2\n")
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [(KERNEL, 0, 400_000)]}]}]}
    waves = [{"t": 0.0, "dims": {"P": 128, "N": 5000, "R": 2}}]
    values = {}
    for name in NEW_METRICS:
        doc = _load(ROOT, "benchmarks", "metrics", name + ".json")
        reader = importlib.import_module(
            f"benchmarks.readers.{doc['reader']}")
        values[name] = reader.read(_ctx(parent, trace, waves), doc["args"])
    assert values == {"encode_groups_ms": None, "wave_groups": 0.0,
                      "spread_peered_pods_share": 0.0,
                      "solve_pallas_groups_roofline": None}


def test_the_groups_roofline_on_a_hand_computed_wave():
    # P = 4 pods, N = 10 nodes, R = 2, G = 3 groups
    # roofline.solve_work: 840 ops, 600 bytes
    # rows: 3 * 11 * 4 = 132; steps: 4 * (4 + 40 + 4) = 192  => 924 bytes
    # ops: + 4 * 10 * 6 = 240                                => 1080 ops
    dims = {"P": 4, "N": 10, "R": 2, "G": 3}
    assert roofline.solve_work(dims) == (840, 600)
    assert roofline_groups.solve_work(dims) == (1080, 924)
    # read through the reader: two launches of 0.4 ms, 100 groups a wave
    doc = _load(ROOT, "benchmarks", "metrics",
                "solve_pallas_groups_roofline.json")
    from benchmarks.readers import roofline_groups as reader
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [(KERNEL, 0, 400_000),
                                       (KERNEL, 10 ** 6, 400_000)]}]}]}
    waves = [{"t": 0.0, "dims": {"P": 128, "N": 5000, "R": 2}}] * 2
    after = ("scheduler_wave_groups_total 300\n"
             "scheduler_wave_solve_seconds_count 3\n")
    ctx = _ctx(after, trace, waves)
    share = reader.read(ctx, doc["args"])
    peaks = roofline.peaks_for("TPU v5 lite")
    least, bound = roofline_groups.least_seconds(
        {"P": 128, "N": 5000, "R": 2, "G": 100.0}, peaks)
    assert bound == "bytes"
    assert abs(share - 100.0 * least / 0.0004) < 1e-9
    assert 0 < share < 5
    assert ctx["notes"]["roofline_groups"]["groups_a_wave"] == 100.0


def test_the_cell_rehearses_at_500_nodes():
    pools = _config()["node_templates"]
    pools[0]["count"] = 500
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 3636), "--seconds", "6",
         "--trace", "1", "--rehearse", "1", "--control", "1",
         "--config-set", "nodes=500",
         "--config-set", f"node_templates={json.dumps(pools)}",
         "--traffic-set", "warm_rounds=[1, 2, 4, 8, 16, 32, 64, 128, 512]"],
        capture_output=True, text=True, cwd=ROOT, timeout=1500)
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert proc.returncode == 0 and lines, proc.stderr[-3000:]
    res = json.loads(lines[-1])
    compared = res["compared"]
    control = compared.pop("control.decisions_differ")
    assert res["rehearsal"] is True and res["metrics"] == {}
    assert res["correct"] is True, compared
    assert all(v["value"] == 0 for v in compared.values())
    assert control["value"] > 100 and res["failed"] == 0
    assert set(NEW_METRICS) - {"solve_pallas_groups_roofline"} \
        <= set(res["names"])
    side = json.loads([ln for ln in proc.stderr.splitlines()
                       if ln.startswith("run.py: {")][-1][len("run.py: "):])
    mix = side["summary"]["by_template"]
    assert len(mix) == 3936
    assert all(m["bound"] == m["attempted"] for m in mix.values())
    # the round of 512 names more groups than the kernel takes rows: it is
    # cut, every wave stays on the kernel, and no wave rebuilds for a group
    assert side["warm_waves"] > 9
    assert set(side["programs"]) == {"pallas@cpu"}
    assert side["resident"]["window"] == \
        {"patched": side["window_waves"]}, side["resident"]
