"""What PR 29 added for the four-chip cell: the two new trace readers on a
hand-made trace of four device planes, the series its counter metrics read
against the program after one wave through the mesh arm, and the shape of
BENCHMARK.json where the generic contract test cannot follow it (an entry's
``workloads`` may now be longer than its accepted metric file's)."""

import importlib
import json
import os

# four virtual devices for the one test that goes through the mesh arm: read
# when JAX first builds its CPU backend, which is after collection
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=4").strip()

import pytest               # noqa: E402

from benchmarks import roofline                                    # noqa: E402
from benchmarks.readers import collective_share, roofline_sharded  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.join(ROOT, "benchmarks")
MS = 1_000_000
MESH_CELL = "mesh-40960n-backlog"
NEW_METRICS = ["sharded_scan_ms", "sharded_scan_roofline", "collective_share",
               "mesh_placed_mb", "scan_sharded_waves_share"]
SCAN = {"line": "XLA Modules", "pattern": r"^jit_run\("}
ALL_REDUCE = ("%all-reduce.7 = s32[1]{0} all-reduce(s32[1]{0} %max.3), "
              "channel_id=1, replica_groups={{0,1,2,3}}")
# an operation that only names a collective among its operands
FUSION = "%fusion.2 = s32[10240]{0} fusion(s32[1]{0} %all-reduce.7), kind=kLoop"


def _plane(i, scan_ms, ops):
    return {"name": f"/device:TPU:{i}", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit_run(123)", 0, scan_ms * MS],
            ["jit_run(123)", 100 * MS, scan_ms * MS],
            ["jit_other(9)", 300 * MS, 50 * MS]]},
        {"name": "XLA Ops", "events": ops}]}


def _trace(ops):
    return {"planes": [_plane(i, 8 + i, ops) for i in range(4)] + [
        {"name": "/host:CPU", "lines": [
            {"name": "XLA Ops", "events": [[ALL_REDUCE, 0, 500 * MS]]}]}]}


WAVES = [{"dims": {"P": 256, "N": 40960, "R": 2, "pods": 200, "nodes": 40960}},
         {"dims": {"P": 128, "N": 40960, "R": 2, "pods": 100, "nodes": 40960}}]


def test_sharded_roofline_gives_each_chip_a_quarter_of_the_nodes():
    ctx = {"trace": _trace([]), "traced_waves": WAVES,
           "device_kind": "TPU v5 lite"}
    # by hand, one chip's share of a wave: N = 10,240 nodes, R = 2
    #   node planes 10,240 * 40 = 409,600 bytes; pod rows P * 32;
    #   mask P * 10,240; outputs P * 8   (all bytes-bound on a v5e)
    b256 = 409_600 + 256 * 32 + 256 * 10_240 + 256 * 8
    b128 = 409_600 + 128 * 32 + 128 * 10_240 + 128 * 8
    assert roofline.solve_work({"P": 256, "N": 10_240, "R": 2})[1] == b256
    least = (b256 + b128) / 2 / 819e9
    # two launches on each of four chips: 2 * (8 + 9 + 10 + 11) ms
    want = 100.0 * least * 8 / 0.076
    assert roofline_sharded.read(ctx, SCAN) == pytest.approx(want)
    assert 0.0 < want < 105.0
    # the one-device reader's arithmetic would hold each chip to the whole
    # wave and read four times as high, less the unsharded pod rows
    whole = sum(roofline.least_seconds(w["dims"], roofline.peaks_for(
        "TPU v5 lite"))[0] for w in WAVES) / 2
    assert 3.5 < whole / least < 4.0


def test_sharded_roofline_finds_nothing_without_an_event_or_a_wave():
    ctx = {"trace": _trace([]), "traced_waves": WAVES,
           "device_kind": "TPU v5 lite"}
    assert roofline_sharded.read(
        ctx, {"line": "XLA Modules", "pattern": "^jit_no_such"}) is None
    assert roofline_sharded.read(dict(ctx, traced_waves=[]), SCAN) is None
    assert roofline_sharded.read({"trace": None}, SCAN) is None


def test_collective_share_is_the_union_of_collectives_over_busy_time():
    ops = [["%while.1 = (s32[]) while((s32[]) %tuple)", 0, 10 * MS],
           [ALL_REDUCE, 1 * MS, 2 * MS],
           [FUSION, 3 * MS, 1 * MS],
           [ALL_REDUCE, 5 * MS, 1 * MS],
           ["%copy.1 = s32[4]{0} copy(s32[4]{0} %p)", 20 * MS, 2 * MS]]
    # busy [0,10) + [20,22) = 12 ms; collectives 2 + 1 = 3 ms
    assert collective_share.read({"trace": _trace(ops)}, {}) == \
        pytest.approx(25.0)


def test_collective_share_of_a_trace_with_no_collective_is_nothing_not_zero():
    ops = [[FUSION, 0, 4 * MS]]
    assert collective_share.read({"trace": _trace(ops)}, {}) is None
    assert collective_share.read({"trace": _trace([])}, {}) is None
    assert collective_share.read({"trace": None}, {}) is None


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _metric_file(name):
    with open(os.path.join(HERE, "metrics", name + ".json")) as f:
        return json.load(f)


def test_every_new_counter_metric_reads_a_number_after_one_mesh_wave():
    import jax

    from kubernetes_tpu.api import types as api
    from kubernetes_tpu.api.quantity import Quantity
    from kubernetes_tpu.models import batch_solver as bs
    from kubernetes_tpu.models.policy import BatchPolicy
    from kubernetes_tpu.models.snapshot import encode_snapshot
    from kubernetes_tpu.parallel import mesh as pmesh
    from kubernetes_tpu.util import metrics

    if len(jax.devices()) < 4:
        pytest.skip("JAX came up with fewer than four devices")
    mesh = pmesh.make_mesh(jax.devices()[:4])
    nodes = [api.Node(metadata=api.ObjectMeta(name=f"node-{i:05d}"),
                      spec=api.NodeSpec(capacity={
                          "cpu": Quantity("4"), "memory": Quantity("32Gi")}))
             for i in range(32_768)]          # past the kernel's 32,640
    pods = [api.Pod(
        metadata=api.ObjectMeta(name=f"pod-{i}", namespace="default",
                                uid=f"uid-{i}"),
        spec=api.PodSpec(containers=[api.Container(
            name="c", image="i", resources=api.ResourceRequirements(limits={
                "cpu": Quantity("100m"), "memory": Quantity("500Mi")}))]))
        for i in range(4)]
    before = metrics.default_registry().render_text()
    bs.solve(encode_snapshot(nodes, [], pods, []), mesh=mesh)
    after = metrics.default_registry().render_text()
    # the wave loop's own count of solves, which this wave went round
    after += "scheduler_wave_solve_seconds_count 1\n"
    ctx = {"metrics_before": before, "metrics_after": after}
    platform = jax.devices()[0].platform
    for name in ("mesh_placed_mb", "scan_sharded_waves_share"):
        doc = _metric_file(name)
        labels = doc["args"]["numerator"].get("labels")
        if labels:
            labels["platform"] = platform     # "tpu" in the file
        reader = importlib.import_module(
            f"benchmarks.readers.{doc['reader']}")
        value = reader.read(ctx, doc["args"])
        assert isinstance(value, float) and value > 0.0, name
    assert f'program="scan-sharded",platform="{platform}"' in after
    # the jitted function's name is the pattern the trace metrics look for
    assert pmesh.sharded_program(mesh, BatchPolicy(), False,
                                 donate=False).__name__ == "run"
    for name in ("sharded_scan_ms", "sharded_scan_roofline"):
        assert _metric_file(name)["args"]["pattern"] == \
            r"^jit_run\("


def test_at_most_half_the_cells_ask_for_four_chips():
    cells = _bench()["workloads"]
    four = [w["name"] for w in cells if w["chips"] == 4]
    assert four == [MESH_CELL] and len(four) <= max(1, len(cells) // 2)


def test_new_metrics_are_the_mesh_cells_and_each_entry_agrees_with_its_file():
    b = _bench()
    cells = [w["name"] for w in b["workloads"]]
    by_name = {m["name"]: m for m in b["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [MESH_CELL] == \
            _metric_file(name)["workloads"]
    for m in b["per_layer"]:
        doc = _metric_file(m["name"])
        assert {k: doc[k] for k in m if k != "workloads"} == \
            {k: v for k, v in m.items() if k != "workloads"}
        # the entry's list starts with what the accepted file had
        had = doc.get("workloads", [])
        assert m["workloads"][:len(had)] == had and \
            set(m["workloads"]) <= set(cells)
    # the Pallas kernel's metrics stay out of the cell that runs none
    for name in ("kernel_ms", "solve_pallas_roofline", "kernel_waves_share"):
        assert MESH_CELL not in by_name[name]["workloads"]
