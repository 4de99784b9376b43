"""The series that PR 27's per-layer metrics read, against the program: one
wave through a BatchScheduler on the CPU (HTTP apiserver, reflectors, the
kernel through the interpreter), then every such metric file's reader has to
return a number from the registries' text — a renamed series fails here, not
as a ``null`` in the ledger. And what the harness takes hold of by name in
the program is there under that name."""

import importlib
import inspect
import json
import os
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.join(ROOT, "benchmarks")
# the metrics that read what the program's phase helper and role marks write
PROGRAM_SPAN_METRICS = [
    "solve_hostprep_ms", "solve_route_ms", "solve_ship_ms",
    "solve_launch_ms", "solve_readback_ms", "solve_post_ms",
    "solve_offcpu_share", "encode_offcpu_share", "commit_offcpu_share",
    "commit_bind_call_ms", "wave_drain_wait_ms", "wave_drain_collect_ms",
    "wave_cut_by_linger_share", "wave_queue_left_pods", "queue_wait_ms",
    "queue_wait_ms.backlog", "cpu_share_wave_loop", "cpu_share_http",
    "cpu_share_watch_send", "cpu_share_reflector", "cpu_share_other"]


def _one_wave_texts():
    from kubernetes_tpu.api import types as api
    from kubernetes_tpu.api.quantity import Quantity
    from kubernetes_tpu.apiserver.http import APIServer
    from kubernetes_tpu.apiserver.master import Master, MasterConfig
    from kubernetes_tpu.client.client import Client
    from kubernetes_tpu.client.http import HTTPTransport
    from kubernetes_tpu.scheduler.driver import ConfigFactory
    from kubernetes_tpu.scheduler.tpu_batch import BatchScheduler
    from kubernetes_tpu.util import metrics

    srv = APIServer(Master(MasterConfig()), host="127.0.0.1", port=0).start()
    factory = sched = None

    def text():
        return (metrics.default_registry().render_text()
                + srv.metrics_registry.render_text())

    try:
        client = Client(HTTPTransport(srv.base_url))
        for i in range(8):
            client.nodes().create(api.Node(
                metadata=api.ObjectMeta(name=f"node-{i}"),
                spec=api.NodeSpec(capacity={"cpu": Quantity("4"),
                                            "memory": Quantity("32Gi")})))
        before = text()
        factory = ConfigFactory(client)
        sched = BatchScheduler(factory.create(), factory, client).run()
        deadline = time.monotonic() + 60.0
        while len(factory.node_store.list()) < 8:
            assert time.monotonic() < deadline
            time.sleep(0.05)
        for i in range(6):
            client.pods("default").create(api.Pod(
                metadata=api.ObjectMeta(name=f"pod-{i}", namespace="default"),
                spec=api.PodSpec(containers=[api.Container(
                    name="c", image="i",
                    resources=api.ResourceRequirements(limits={
                        "cpu": Quantity("100m"),
                        "memory": Quantity("500Mi")}))])))
        while not all(p.spec.host for p in client.pods("default").list().items):
            assert time.monotonic() < deadline, "the wave never bound"
            time.sleep(0.05)
        return before, text()
    finally:
        if sched is not None:
            sched.stop()
        if factory is not None:
            factory.stop()
        srv.stop()


def test_every_program_span_metric_reads_a_number_after_one_wave():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"] for m in json.load(f)["per_layer"]}
    assert set(PROGRAM_SPAN_METRICS) <= listed
    before, after = _one_wave_texts()
    ctx = {"metrics_before": before, "metrics_after": after}
    values = {}
    for name in PROGRAM_SPAN_METRICS:
        with open(os.path.join(HERE, "metrics", name + ".json")) as f:
            doc = json.load(f)
        assert doc["better"] == "lower"
        reader = importlib.import_module(
            f"benchmarks.readers.{doc['reader']}")
        values[name] = reader.read(ctx, doc["args"])
    missing = [n for n, v in values.items() if not isinstance(v, float)]
    assert not missing, f"no number for {missing}"
    assert all(v >= 0.0 for v in values.values()), values
    # each part is observed once a wave, so the six are means over the same
    # waves as the solve's own histogram and add up inside it
    solve_ms = importlib.import_module(
        "benchmarks.readers.histogram_mean").read(
            ctx, {"series": "scheduler_wave_solve_seconds", "scale": 1000})
    parts = sum(values[f"solve_{p}_ms"] for p in (
        "hostprep", "route", "ship", "launch", "readback", "post"))
    assert 0.0 < parts <= solve_ms
    assert sum(v for n, v in values.items()
               if n.startswith("cpu_share_")) > 0.0
    assert all(0.0 <= values[n] <= 100.0 for n in (
        "solve_offcpu_share", "encode_offcpu_share", "commit_offcpu_share",
        "wave_cut_by_linger_share"))


def test_the_program_keeps_the_names_the_harness_takes_hold_of():
    from benchmarks.harness import control_plane as cpl
    from kubernetes_tpu.models.batch_solver import wave_programs
    from kubernetes_tpu.scheduler.driver import SchedulerConfig
    from kubernetes_tpu.scheduler.tpu_batch import BatchScheduler

    for attr in list(cpl.WAVE_PHASES) + ["_default_solve"]:
        assert callable(getattr(BatchScheduler, attr)), attr
    assert "tctx" in inspect.signature(
        BatchScheduler._default_solve).parameters
    assert "next_pod" in SchedulerConfig.__dataclass_fields__
    # program_counts() unpacks each label tuple as a pair
    assert wave_programs().label_names == ("program", "platform")
    # the loop reaches its phases through the instance, where the harness
    # has put its own
    src = inspect.getsource(BatchScheduler.schedule_wave) + \
        inspect.getsource(BatchScheduler._default_solve)
    for attr in cpl.WAVE_PHASES:
        assert f"self.{attr}(" in src, attr
