"""Warm-start (util/warmstart): restart skips once-per-shape costs.

Covers the WaveRouter calibration store roundtrip (per-shape plans keyed
by the stable repr of (shapes, policy, gangs, eligibility)), corruption
tolerance, and the env gates. The JAX persistent compilation cache side
is config-only (jax owns the cache itself) — asserted via the config
value, not by timing compiles."""

import json
import os


from kubernetes_tpu.models.batch_solver import WavePlan, WaveRouter
from kubernetes_tpu.models.policy import BatchPolicy
from kubernetes_tpu.util import warmstart


def _key(n=4):
    return ((("<i4", (n, 2)), ("<u4", (n, 1))), BatchPolicy(), False, True)


def test_router_calibration_roundtrip(tmp_path):
    path = str(tmp_path / "router_cal.json")
    r1 = WaveRouter()
    r1.load_calibrations(path)          # absent file: 0 entries, path set
    r1._plans[_key()] = WavePlan("device", None, 0.5, 0.2, 1.5)
    r1._plans[_key(8)] = WavePlan("host", object(), 0.1, 0.4, 0.9)
    r1.save_calibrations()

    r2 = WaveRouter()
    assert r2.load_calibrations(path) == 2
    plan = r2._from_persisted(_key(), cpu=None)
    assert plan is not None and plan.path == "device"
    assert plan.device_s == 0.2 and plan.cold_s == 1.5
    # a restored plan enters the in-memory cache (no re-read per wave)
    assert r2._plans[_key()] is plan
    host_plan = r2._from_persisted(_key(8), cpu="fake-cpu-device")
    assert host_plan.path == "host" and host_plan.device == "fake-cpu-device"


def test_router_calibration_uncalibrated_plans_not_persisted(tmp_path):
    path = str(tmp_path / "router_cal.json")
    r = WaveRouter()
    r.load_calibrations(path)
    nan = float("nan")
    r._plans[_key()] = WavePlan("device", None, nan, nan, nan)  # forced mode
    r.save_calibrations()
    r2 = WaveRouter()
    assert r2.load_calibrations(path) == 0


def test_router_calibration_tolerates_corruption(tmp_path):
    path = str(tmp_path / "router_cal.json")
    with open(path, "w") as fh:
        fh.write("{not json")
    r = WaveRouter()
    assert r.load_calibrations(path) == 0
    with open(path, "w") as fh:
        json.dump({"v": 99, "plans": {"x": {}}}, fh)  # version skew
    assert r.load_calibrations(path) == 0


def test_warmstart_env_gates(monkeypatch, tmp_path):
    monkeypatch.setenv("KTPU_WARM_START", "off")
    assert not warmstart.enabled()
    assert warmstart.enable() is None
    monkeypatch.setenv("KTPU_WARM_START", "auto")
    assert warmstart.enabled()
    monkeypatch.setenv("KTPU_CACHE_DIR", str(tmp_path / "cache"))
    assert warmstart.cache_dir() == str(tmp_path / "cache")
    assert warmstart.router_cal_path().endswith("router_cal.json")


def test_warmstart_default_dir_is_repo_local(monkeypatch):
    monkeypatch.delenv("KTPU_CACHE_DIR", raising=False)
    d = warmstart.cache_dir()
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(warmstart.__file__))))
    assert d == os.path.join(repo, ".ktpu_cache")


def _fresh_enable(monkeypatch):
    """enable() as a new process would run it, with its process-global
    effects (the idempotence latch, the default router's store, jax's
    threshold) put back afterwards."""
    import jax

    from kubernetes_tpu.models.batch_solver import default_router
    monkeypatch.setattr(warmstart, "_active_dir", None)
    monkeypatch.setattr(default_router, "_cal_path", None)
    monkeypatch.setenv("KTPU_WARM_START", "auto")
    return jax, (jax.config.jax_compilation_cache_dir,
                 jax.config.jax_persistent_cache_min_compile_time_secs)


def _restore(jax, saved):
    jax.config.update("jax_compilation_cache_dir", saved[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", saved[1])


def test_enable_leaves_jax_cache_dir_alone_when_env_sets_it(monkeypatch,
                                                            tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set the program keeps its
    compile cache there and sets no other in code; the calibration
    stores still live under cache_dir()."""
    jax, saved = _fresh_enable(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "theirs"))
    base = str(tmp_path / "ktpu")
    try:
        assert warmstart.enable(base) == base
        assert jax.config.jax_compilation_cache_dir == saved[0]
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
        assert os.path.isdir(base)
        assert not os.path.exists(os.path.join(base, "jax"))
    finally:
        _restore(jax, saved)


def test_enable_sets_the_fixed_dir_when_env_is_silent(monkeypatch, tmp_path):
    jax, saved = _fresh_enable(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    base = str(tmp_path / "ktpu")
    try:
        assert warmstart.enable(base) == base
        assert jax.config.jax_compilation_cache_dir == \
            os.path.join(base, "jax")
        assert os.path.isdir(os.path.join(base, "jax"))
    finally:
        _restore(jax, saved)
