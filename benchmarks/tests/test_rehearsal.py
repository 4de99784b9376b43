"""run.py end to end without the chip, at 50 nodes on the CPU backend with
the kernel through the interpreter: feeder child, window, wave recorder,
final LIST, reference, a well-formed last line. Then the same run with the
timed path broken underneath — an answer altered where it is produced, a
step that leaves its state unchanged — which has to come out as not
correct. And the fixture (``fixtures/three-class-5000n.json``: three machine
types, three kinds of pod with a node selector, a host port and a service)
cut to 60 nodes, through the same path, with ``serial_default`` deciding. A
rehearsal finds wrong paths; it says nothing about the chip, and prints no
metric."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py")]
SMALL = ["--rehearse", "1", "--config-set", "nodes=50",
         "--traffic-set", "warm_rounds=[1, 2, 4, 8]"]


def _fixture_args(counts=(36, 18, 6), rate=40):
    """The fixture's keys as run.py takes them: by --config-set."""
    with open(os.path.join(ROOT, "benchmarks", "tests", "fixtures",
                           "three-class-5000n.json")) as f:
        doc = json.load(f)
    doc["nodes"] = sum(counts)
    for template, count in zip(doc["node_templates"], counts):
        template["count"] = count
    args = ["--rehearse", "1", "--traffic-set", "warm_rounds=[1, 2, 4, 8]",
            "--traffic-set", f"rate={rate}"]
    for key in ("nodes", "node_templates", "pod_templates", "services",
                "reference"):
        args += ["--config-set", f"{key}={json.dumps(doc[key])}"]
    return args


def _steady_cell():
    return [w["name"] for w in _bench()["workloads"]
            if "steady" in w["traffic"]][0]


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _last_line(proc):
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert proc.returncode == 0 and lines, proc.stderr[-3000:]
    return json.loads(lines[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_ends_in_a_well_formed_last_line(trace):
    cell = [w["name"] for w in _bench()["workloads"]
            if "steady" in w["traffic"]][0]
    proc = subprocess.run(
        RUN + ["--workload", cell, "--seed", str(2 ** 31 + 12345),
               "--seconds", "4", "--trace", str(trace)] + SMALL,
        capture_output=True, text=True, cwd=ROOT, timeout=600)
    res = _last_line(proc)
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device", "compared"}
    assert res["rehearsal"] is True and res["metrics"] == {}
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] > 100 and res["failed"] == 0
    assert list(res)[-1] == "compared"
    assert all(v["value"] <= v["limit"] for v in res["compared"].values())
    # each number compared stands beside its limit at the end of stderr
    tail = proc.stderr.strip().splitlines()[-len(res["compared"]):]
    assert all(ln.startswith("compared ") and "(limit 0)" in ln
               for ln in tail)
    want = {m["name"] for m in _bench()[
        "per_layer" if trace else "end_to_end"]
        if m["source"] != "device_trace"
        and cell in m.get("workloads", [cell])}
    # a reader that finds nothing to read leaves its metric out: on the CPU
    # no wave runs on pallas@tpu
    assert set(res["names"]) == want - {"kernel_waves_share"} or \
        set(res["names"]) == want


def test_without_a_tpu_and_without_the_rehearsal_flag_nothing_is_printed():
    cell = _bench()["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(RUN + ["--workload", cell, "--seed", "1",
                                 "--seconds", "1", "--trace", "0"],
                          capture_output=True, text=True, cwd=ROOT, env=env,
                          timeout=300)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "needs 1 TPU chip" in proc.stderr


def _alter_an_answer(monkeypatch):
    """In every wave of two pods or more the first pod is sent where the
    second one goes."""
    from kubernetes_tpu.scheduler import tpu_batch

    inner = tpu_batch.BatchScheduler._solve_snap

    def altered(self, snap, n_pending, tctx=None):
        d = inner(self, snap, n_pending, tctx=tctx)
        hosts = list(d.hosts)
        if len(hosts) >= 2 and hosts[0] != hosts[1] and hosts[1]:
            hosts[0] = hosts[1]
        return d._replace(hosts=hosts)

    monkeypatch.setattr(tpu_batch.BatchScheduler, "_solve_snap", altered)


def _leave_the_state_unchanged(monkeypatch):
    """The pods a wave has bound never reach the encoder's planes: every
    wave decides against the cluster as the first wave found it."""
    from kubernetes_tpu.models import incremental

    inner = incremental.IncrementalEncoder.encode_delta

    def unchanged(self, nodes, upserted, removed, pending_pods, *a, **kw):
        return inner(self, nodes, [], removed, pending_pods, *a, **kw)

    monkeypatch.setattr(incremental.IncrementalEncoder, "encode_delta",
                        unchanged)


@pytest.mark.parametrize("plant", [_alter_an_answer,
                                   _leave_the_state_unchanged])
def test_a_run_with_the_timed_path_broken_underneath_is_not_correct(
        plant, monkeypatch, capsys):
    """The harness's look for a chip skipped (--rehearse), the rest of a run
    driven in this process with a fault planted under the scheduler: an
    answer altered where it is produced; a step that returns its state
    unchanged. (Half a batch left out and an exchange between chips left out
    are faults this cell cannot have: a wave leaves no pod out, it requeues
    it, and one chip exchanges nothing.)"""
    plant(monkeypatch)
    from benchmarks import run as bench_run
    cell = [w["name"] for w in _bench()["workloads"]
            if "steady" in w["traffic"]][0]
    rc = bench_run.main(["--workload", cell, "--seed", "77",
                         "--seconds", "3", "--trace", "0"] + SMALL)
    out = capsys.readouterr().out.strip().splitlines()
    res = json.loads(out[-1])
    assert rc == 0 and res["correct"] is False
    assert res["compared"]["decisions_differ"]["value"] >= 1


def test_the_fixture_runs_through_with_every_template_bound_in_proportion():
    proc = subprocess.run(
        RUN + ["--workload", _steady_cell(), "--seed", str(2 ** 31 + 99),
               "--seconds", "4", "--trace", "0", "--control", "1"]
        + _fixture_args(), capture_output=True, text=True, cwd=ROOT,
        timeout=600)
    res = _last_line(proc)
    compared = res["compared"]
    control = compared.pop("control.decisions_differ")
    assert res["correct"] is True, compared
    assert all(v["value"] == 0 for v in compared.values())
    assert control["value"] > 0 and control["limit"] is None
    side = json.loads([ln for ln in proc.stderr.splitlines()
                       if ln.startswith("run.py: {")][-1][len("run.py: "):])
    assert side["overrides"]["config.reference"] == "serial_default"
    mix = side["summary"]["by_template"]
    assert set(mix) == {"small", "zoned", "ported"}
    assert all(m["bound"] == m["attempted"] > 0 for m in mix.values())
    offered = sum(m["attempted"] for m in mix.values())
    assert offered == res["attempted"] and res["failed"] == 0
    # 6 : 3 : 1 over whole blocks; a window ends inside one
    assert mix["small"]["attempted"] > mix["zoned"]["attempted"] \
        > mix["ported"]["attempted"]


def test_a_selector_the_reference_does_not_see_reads_as_decisions_that_differ(
        monkeypatch, capsys):
    """The comparison sees the new fields: the same run, the 'zoned'
    template's node selector dropped on the reference's side only."""
    from benchmarks import run as bench_run
    from benchmarks.harness import correct as cor

    inner = cor._pod_of

    def blind(feeder_doc):
        doc = dict(feeder_doc, pod_templates=[
            dict(t, node_selector={}) for t in feeder_doc["pod_templates"]])
        return inner(doc)

    monkeypatch.setattr(cor, "_pod_of", blind)
    rc = bench_run.main(["--workload", _steady_cell(), "--seed", "78",
                         "--seconds", "3", "--trace", "0"] + _fixture_args())
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is False
    assert res["compared"]["decisions_differ"]["value"] >= 1
