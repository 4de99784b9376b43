"""``sched-mixed-5000n`` and its cell ``mixed-5000n-backlog`` (PR 34;
upstream's ``SchedulingNodeAffinity`` at 5,000 nodes): what the feeder posts
and the control plane registers for the configuration is byte for byte what
PR 34's tree posted and registered (``goldens/sched-mixed-5000n.json``,
captured there: the pod, no service, the nodes); the cell rehearses end to
end on the CPU at 500 nodes, ``serial_default`` deciding; and its three
per-layer metrics have file, reader and cells."""

import hashlib
import importlib
import json
import os
import subprocess
import sys

from benchmarks import feeder
from benchmarks.harness import control_plane as cpl
from benchmarks.harness import deployment as dep

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CONFIG, CELL = "sched-mixed-5000n", "mixed-5000n-backlog"
NEW_METRICS = ("constrained_pods_share", "encode_pods_ms",
               "prewarm_compiles_per_wave")
TEMPLATE = "pod-with-node-affinity"
ZONE = {"topology.kubernetes.io/zone": "zone1"}


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _encoder():
    from kubernetes_tpu.client.http import HTTPTransport
    t = HTTPTransport("http://127.0.0.1:1")
    return lambda obj: t.scheme.encode(obj, t.version)


def posted(config: dict, seed: int) -> dict:
    """What a run of this configuration with this seed posts: the first pod
    of every template in the window plan's order, the services, the nodes."""
    encode = _encoder()
    templates = dep.pod_templates(config)
    plans = {"window": dep.pod_plan(templates, "window", seed, 1)}
    factory = feeder.PodFactory(templates, plans, seed)
    pods = {}
    for _ in range(len(plans["window"])):
        index, pod = factory.make()
        pods.setdefault(templates[index]["name"], encode(pod))
    nodes = [encode(n) for n in cpl.make_nodes(config, seed)]
    return {"seed": seed, "pods": pods,
            "plan_first": [templates[i]["name"]
                           for i in plans["window"][:40]],
            "services": [encode(s) for s in cpl.make_services(config)],
            "nodes": len(nodes), "nodes_first": nodes[:3],
            "nodes_sha256": hashlib.sha256(
                "\n".join(nodes).encode()).hexdigest()}


def test_the_configuration_posts_what_pr_34_posted():
    config = _load(ROOT, "benchmarks", "configs", CONFIG + ".json")
    golden = _load(HERE, "goldens", CONFIG + ".json")
    assert posted(config, golden["seed"]) == golden
    assert list(golden["pods"]) == [TEMPLATE]
    pod = json.loads(golden["pods"][TEMPLATE])
    assert pod["spec"]["nodeSelector"] == ZONE
    assert "labels" not in pod["metadata"]
    assert "ports" not in pod["spec"]["containers"][0]
    assert golden["services"] == []
    assert all(json.loads(n)["metadata"]["labels"] == ZONE
               for n in golden["nodes_first"])


def test_the_pod_and_the_node_are_the_control_s_but_for_the_zone():
    """``sched-basic-5000n`` under the same seed: the same names, uids and
    shapes; the pod gains its selector, the node its label."""
    golden = _load(HERE, "goldens", CONFIG + ".json")
    basic = posted(_load(ROOT, "benchmarks", "configs",
                         "sched-basic-5000n.json"), golden["seed"])
    pod = json.loads(golden["pods"][TEMPLATE])
    del pod["spec"]["nodeSelector"]
    assert pod == json.loads(basic["pods"]["default"])
    for mine, theirs in zip(golden["nodes_first"], basic["nodes_first"]):
        node = json.loads(mine)
        del node["metadata"]["labels"]
        assert node == json.loads(theirs)


def test_the_three_new_metrics_have_file_reader_and_cells():
    bench = _load(ROOT, "BENCHMARK.json")
    entries = {m["name"]: m for m in bench["per_layer"]}
    control = "basic-5000n-backlog"
    for name in NEW_METRICS:
        entry = entries[name]
        doc = _load(ROOT, "benchmarks", "metrics", name + ".json")
        # the entry's cells are BENCHMARK.json's to widen (PR 36 added its
        # cell there); the file keeps the cells it was written with
        same = set(entry) - {"workloads"}
        assert {k: doc[k] for k in same} == {k: entry[k] for k in same}
        assert {control, CELL} <= set(entry["workloads"])
        assert entry["moves"] == "pods_per_s"
        reader = importlib.import_module(
            f"benchmarks.readers.{doc['reader']}")
        assert callable(reader.read)
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "backlog", 1)
    # the cell reports whatever its control reports, but the metrics of
    # service groups (PR 36): this cell has no service
    groups = {"encode_groups_ms", "wave_groups", "spread_peered_pods_share"}
    for m in bench["per_layer"]:
        if control in m.get("workloads", []) and m["name"] not in groups:
            assert CELL in m["workloads"], m["name"]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    # a reader that finds no such series on the parent returns nothing or
    # zero and does not raise
    empty = {"metrics_before": "", "metrics_after":
             "scheduler_wave_pods_total 10\n"
             "scheduler_wave_solve_seconds_count 2\n"}
    values = {}
    for name in NEW_METRICS:
        doc = _load(ROOT, "benchmarks", "metrics", name + ".json")
        reader = importlib.import_module(
            f"benchmarks.readers.{doc['reader']}")
        values[name] = reader.read(empty, doc["args"])
    assert values == {"constrained_pods_share": 0.0, "encode_pods_ms": None,
                      "prewarm_compiles_per_wave": 0.0}


def test_the_cell_rehearses_at_500_nodes():
    config = _load(ROOT, "benchmarks", "configs", CONFIG + ".json")
    pools = config["node_templates"]
    pools[0]["count"] = 500
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 3434), "--seconds", "6",
         "--trace", "1", "--rehearse", "1", "--control", "1",
         "--config-set", "nodes=500",
         "--config-set", f"node_templates={json.dumps(pools)}",
         "--traffic-set", "warm_rounds=[1, 2, 4, 8, 16, 32, 64, 128]"],
        capture_output=True, text=True, cwd=ROOT, timeout=900)
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert proc.returncode == 0 and lines, proc.stderr[-3000:]
    res = json.loads(lines[-1])
    compared = res["compared"]
    control = compared.pop("control.decisions_differ")
    assert res["rehearsal"] is True and res["metrics"] == {}
    assert res["correct"] is True, compared
    assert all(v["value"] == 0 for v in compared.values())
    assert control["value"] > 100 and res["failed"] == 0
    assert set(NEW_METRICS) <= set(res["names"])
    side = json.loads([ln for ln in proc.stderr.splitlines()
                       if ln.startswith("run.py: {")][-1][len("run.py: "):])
    mix = side["summary"]["by_template"]
    assert list(mix) == [TEMPLATE]
    assert mix[TEMPLATE]["bound"] == mix[TEMPLATE]["attempted"] > 0
    # the selector's column lands in the warm-up rounds
    assert side["resident"]["window"] == \
        {"patched": side["window_waves"]}, side["resident"]
