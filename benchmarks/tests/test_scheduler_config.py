"""A configuration states its scheduler (``harness/deployment.scheduler``):
the harness builds it as ``cmd/scheduler.py`` builds it from
``--algorithm-provider`` and ``--policy-config-file``
(``control_plane.build_scheduler``), and the reference sees the Policy
(``correct.replay``). The accepted configurations state no Policy and get
exactly the calls they got before: the default provider, no Policy, the
default batch policy. A plugin outside the vocabulary ends a run before
any process starts; a Policy the wave scheduler cannot model ends it as a
BenchFailure, with no fallback to the serial driver. (The policy
fixture's rehearsal is in ``test_load_cell.py``, beside the other runs of
``load-5000n-backlog``: runs of one cell share ``.bench_out/<cell>``.)"""

import inspect
import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import control_plane as cpl
from benchmarks.harness import correct
from benchmarks.harness import deployment as dep

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CONFIGS = ["sched-basic-500n", "sched-basic-5000n", "sched-basic-40960n",
           "sched-mixed-5000n", "sched-load-5000n"]


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


FIXTURE = _load(HERE, "fixtures", "policy-load-5000n.json")


class _Factory:
    def __init__(self):
        self.calls = []

    def create(self, **kw):
        self.calls.append(kw)
        return "scheduler-config"


class _BatchScheduler:
    made: list = []

    def __init__(self, *args, **kw):
        _BatchScheduler.made.append((args, kw))


@pytest.fixture
def fake_scheduler(monkeypatch):
    from kubernetes_tpu.scheduler import tpu_batch
    _BatchScheduler.made = []
    monkeypatch.setattr(tpu_batch, "BatchScheduler", _BatchScheduler)
    return _BatchScheduler


@pytest.mark.parametrize("name", CONFIGS)
def test_an_accepted_configuration_builds_the_scheduler_it_built(
        name, fake_scheduler):
    from kubernetes_tpu.models.policy import (DEFAULT_BATCH_POLICY,
                                              batch_policy_from)
    from kubernetes_tpu.scheduler.driver import ConfigFactory

    config = _load(ROOT, "benchmarks", "configs", name + ".json")
    assert dep.scheduler(config) == {"provider": "DefaultProvider",
                                     "policy": None}
    factory = _Factory()
    cpl.build_scheduler(config, factory, "client", "recorder")
    assert factory.calls == [{"provider": "DefaultProvider", "policy": None,
                              "recorder": "recorder"}]
    (args, kw), = fake_scheduler.made
    assert args == ("scheduler-config", factory, "client")
    assert kw == {"batch_policy": DEFAULT_BATCH_POLICY}
    # what the harness passed before: create(recorder=) and no batch policy,
    # so the provider and the Policy were create's defaults and the batch
    # policy the one BatchScheduler derives from them
    defaults = inspect.signature(ConfigFactory.create).parameters
    assert (defaults["provider"].default, defaults["policy"].default) == \
        ("DefaultProvider", None)
    assert batch_policy_from("DefaultProvider", None) == DEFAULT_BATCH_POLICY


def test_the_control_plane_builds_its_scheduler_there(monkeypatch):
    seen = []

    class Built(Exception):
        pass

    def build(config, factory, client, recorder):
        seen.append((config, factory, client, recorder))
        raise Built

    monkeypatch.setattr(cpl, "build_scheduler", build)
    config = dict(_load(ROOT, "benchmarks", "configs",
                        "sched-basic-500n.json"), nodes=2)
    with pytest.raises(Built):
        cpl.ControlPlane(config, 7, clog=None)
    (got, factory, client, recorder), = seen
    assert got is config
    assert factory.client is client
    assert isinstance(recorder, cpl.CountingRecorder)


def test_a_policy_reaches_the_factory_the_scheduler_and_the_reference(
        fake_scheduler):
    from kubernetes_tpu.scheduler import plugins

    config = _load(ROOT, "benchmarks", "configs", "sched-load-5000n.json")
    config.update({k: FIXTURE[k] for k in ("nodes", "node_templates",
                                           "scheduler", "reference")})
    factory = _Factory()
    cpl.build_scheduler(config, factory, "client", "recorder")
    (call,) = factory.calls
    assert call["provider"] == "DefaultProvider"
    assert call["policy"] == plugins.load_policy(
        json.dumps(FIXTURE["scheduler"]["policy"]))
    (_args, kw), = fake_scheduler.made
    pol = kw["batch_policy"]
    assert (pol.affinity_labels, pol.anti_affinity, pol.w_lr, pol.w_spread,
            pol.use_disk, pol.use_host) == \
        (("region",), (("zone", 2),), 1, 1, True, False)
    # the reference is handed the Policy as the file states it
    ref = correct.load_reference(config)
    wave = {"pods": ["p"]}
    template = dep.pod_templates(config)[0]
    (host, _), = correct.replay(ref, config, [wave], {"p": ("u", template)})[0]
    labels = dep.nodes_of(config)[host]["labels"]
    assert set(labels) == {"region", "zone"}


@pytest.mark.parametrize("policy", [
    {"predicates": [{"name": "CheckVolumeCount"}]},
    {"priorities": [{"name": "MostRequestedPriority", "weight": 1}]},
    {"predicates": [{"name": "Region", "argument": {
        "serviceAffinity": {"labels": ["region"]},
        "labelsPresence": {"labels": ["region"]}}}]},
    {"priorities": [{"name": "Zone", "argument": {
        "serviceAntiAffinity": {"labels": ["zone"]}}}]},
    {"priorities": [{"name": "EqualPriority", "weight": 1.5}]},
    {"predicates": [{"name": "PodFitsResources", "weight": 1}]},
    {"predicate": []}])
def test_a_plugin_outside_the_vocabulary_is_a_config_error(policy):
    with pytest.raises(dep.ConfigError):
        dep.scheduler({"scheduler": {"policy": policy}})


def test_a_provider_there_is_none_of_is_a_config_error():
    with pytest.raises(dep.ConfigError):
        dep.scheduler({"scheduler": {"provider": "ClusterAutoscalerProvider"}})


def test_run_py_ends_at_a_config_error_before_any_process_starts():
    bad = {"policy": {"predicates": [{"name": "CheckVolumeCount"}]}}
    # a cell no other test runs: the override file it writes first is
    # named after the cell's configuration, and runs share it
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "mesh-40960n-backlog", "--seed", "1", "--seconds", "1",
         "--rehearse", "1", "--config-set", f"scheduler={json.dumps(bad)}"],
        capture_output=True, text=True, cwd=ROOT, timeout=120)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "ConfigError" in proc.stderr and "CheckVolumeCount" in proc.stderr
    assert "jax" not in proc.stderr.split("ConfigError")[0].lower()


def test_a_policy_the_wave_scheduler_cannot_model_is_a_bench_failure(
        fake_scheduler):
    config = {"scheduler": {"policy": {"priorities": [
        {"name": "LeastRequestedPriority", "weight": -1}]}}}
    factory = _Factory()
    with pytest.raises(cpl.BenchFailure, match="negative priority weight"):
        cpl.build_scheduler(config, factory, "client", "recorder")
    assert factory.calls == [] and fake_scheduler.made == []
