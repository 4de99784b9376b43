"""chip_smoke.py rehearsed without the chip: its phases at a tiny size on
the CPU backend (the Pallas kernel through the interpreter), the
four-chip phase on four of the eight virtual devices, and main()'s
refusals. A rehearsal finds wrong paths, arguments and control flow; it
says nothing about the chip."""

import json
import os
import subprocess
import sys
import time
import types

import jax
import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def clog():
    return chip_smoke.CompileLog()


@pytest.fixture
def interpret_kernel(monkeypatch):
    monkeypatch.setenv("KTPU_PALLAS", "interpret")


def test_phase_solve_rehearsal(clog, interpret_kernel):
    rec = chip_smoke.phase_solve(clog, 128, 96, oracle_pods=16, warm_runs=1)
    assert rec["ok"] and rec["programs"] == {"pallas@cpu": 2}
    assert rec["equal_to_scan"] == "96/96"
    assert rec["equal_to_oracle"] == "16/16"
    assert rec["route"] == "device" and rec["scheduled"] == 96
    assert set(rec["compile_s"]) >= {"kernel", "scan"}
    json.dumps(chip_smoke._jsonable(rec))


def test_phase_solve_fails_when_the_kernel_gives_way(clog, monkeypatch):
    """KTPU_PALLAS=auto on a CPU backend: solve_device drops to the scan
    without a word. The phase must see it and fail."""
    monkeypatch.setenv("KTPU_PALLAS", "auto")
    with pytest.raises(chip_smoke.SmokeFailure, match="Pallas kernel"):
        chip_smoke.phase_solve(clog, 32, 16, oracle_pods=4, warm_runs=1)


def test_phase_served_rehearsal(clog, interpret_kernel):
    """Two waves (1,024 + 76) over real HTTP: the second rides the
    incremental encoder's delta path."""
    rec = chip_smoke.phase_served(clog, 128, 1_100, feeders=2,
                                  timeout_s=300.0)
    assert rec["ok"] and rec["pods_bound"] == "1100/1100"
    assert rec["waves"] == 2 and rec["programs"] == {"pallas@cpu": 2}
    assert rec["bindings_batch_requests"] == {"200": 2}
    assert rec["events"] == {"Scheduled": 1_100}
    assert rec["nodes_used"] == 128 and rec["host_port_pods"] == 110
    assert "KTPU_WAVE_ROUTER" not in os.environ   # the pin was put back


def test_final_list_check_catches_an_overcommitted_node():
    import bench
    nodes, _, pods, _ = bench.build_cluster(2, 80, existing_per_node=0)
    for p in pods:                       # 80 pods x >= 100m on one 16-cpu node
        p.spec.host = p.status.host = nodes[0].metadata.name
    with pytest.raises(chip_smoke.SmokeFailure,
                       match="host port|over capacity"):
        chip_smoke._check_final_list(pods, nodes, 80)
    for p in pods:
        p.spec.containers[0].ports = []
    with pytest.raises(chip_smoke.SmokeFailure, match="over capacity"):
        chip_smoke._check_final_list(pods, nodes, 80)


def test_router_calibration_without_a_host_route():
    """On a CPU default backend the router has nothing to compare."""
    rec = chip_smoke.router_calibration(32, 16)
    assert rec["calibrated"] is False and rec["winner"] == "device"
    assert chip_smoke._jsonable(rec)["host_s"] is None


def test_phase_mesh_on_four_of_eight_virtual_devices(clog, monkeypatch):
    all_devices = jax.devices()
    assert len(all_devices) == 8      # tests/conftest.py
    monkeypatch.setattr(jax, "devices",
                        lambda *a, **kw: all_devices[:4])
    rec = chip_smoke.phase_mesh(clog, 128, 32)
    assert rec["mesh"] == {"pods": 1, "nodes": 4}
    assert rec["mesh_devices"] == [d.id for d in all_devices[:4]]
    assert rec["solve_sharded_equal"] == "32/32"
    assert rec["mesh_exec_full_equal"] == "32/32"
    assert rec["mesh_exec_delta_equal"] == "32/32"
    assert rec["mesh_exec"]["delta_reshard_bytes"] == 0
    for planes in (rec["solve_sharded_planes"],
                   rec["mesh_exec_resident_planes"]):
        assert planes["devices"] == rec["mesh_devices"]
        assert planes["shard_shapes"]["cap"] == [32, 2]   # 128 nodes / 4


def _fake_tpu(monkeypatch, count=1):
    dev = types.SimpleNamespace(platform="tpu", device_kind="fake v5e")
    monkeypatch.setattr(jax, "devices", lambda *a, **kw: [dev] * count)
    from kubernetes_tpu.util import warmstart
    monkeypatch.setattr(warmstart, "enable", lambda *a, **kw: None)
    return {"platform": "tpu", "kind": "fake v5e", "count": count}


def test_main_last_line_is_the_contract(monkeypatch, capsys):
    device = _fake_tpu(monkeypatch)
    for name in ("phase_solve", "phase_served", "router_calibration"):
        monkeypatch.setattr(chip_smoke, name,
                            lambda *a, _n=name, **kw: {"phase": _n})
    monkeypatch.setattr(chip_smoke, "phase_mesh", lambda *a, **kw: 1 / 0)
    assert chip_smoke.main([]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert [json.loads(ln).get("phase") for ln in lines[:-1]] == [
        "start", "phase_solve", "phase_served", "router_calibration"]
    assert json.loads(lines[-1]) == {"ok": True, "device": device}


def test_main_four_chips_runs_only_the_mesh_phase(monkeypatch, capsys):
    device = _fake_tpu(monkeypatch, count=4)
    for name in ("phase_solve", "phase_served", "router_calibration"):
        monkeypatch.setattr(chip_smoke, name, lambda *a, **kw: 1 / 0)
    monkeypatch.setattr(chip_smoke, "phase_mesh",
                        lambda *a, **kw: {"phase": "mesh"})
    assert chip_smoke.main(["--four-chips"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert [json.loads(ln).get("phase") for ln in lines[:-1]] == [
        "start", "mesh"]
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    _fake_tpu(monkeypatch, count=1)
    assert chip_smoke.main(["--four-chips"]) == 1
    assert '"ok"' not in capsys.readouterr().out


def test_main_a_phase_that_raises_ends_the_run(monkeypatch, capsys):
    _fake_tpu(monkeypatch)
    monkeypatch.setattr(chip_smoke, "phase_solve",
                        lambda *a, **kw: {"phase": "solve"})

    def broken(*a, **kw):
        raise chip_smoke.SmokeFailure("pod p bound twice")
    monkeypatch.setattr(chip_smoke, "phase_served", broken)
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


def test_script_refuses_a_machine_without_a_tpu():
    """As the driver runs it in the sandbox: no accelerator, so a quick
    non-zero exit and no result on stdout."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    p = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=env, cwd=REPO, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout and p.stdout.strip() == ""
    assert "no TPU" in p.stderr
    assert time.monotonic() - t0 < 60
