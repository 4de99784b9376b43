"""kube-explain — batched unschedulability diagnosis from the dense
planes.

The contract under test (models/explain.py attribution contract):

- per-pod per-filter node-elimination counts bit-identical to the
  oracle.explain_serial twin across full / empty / tied / preemption
  fixtures and fuzz (full AND incremental encoders);
- the FailedScheduling event carries the k8s-idiom top-k line
  (``0/N nodes available: ...``) end-to-end through a live
  BatchScheduler, with zero new plumbing past the recorder;
- diagnosis stays off the hot path: rate-limited, never invoked when
  every pod binds, and declined waves still count every pod in the
  unschedulable metric families (reason ``unexplained``);
- the ``failed_scheduling_burst`` SLO rule fires and resolves on the
  unschedulable-rate curve.
"""

import random
import time
import types

from kubernetes_tpu.api import types as api
from kubernetes_tpu.api.quantity import Quantity
from kubernetes_tpu.apiserver.master import Master
from kubernetes_tpu.client.client import Client, InProcessTransport
from kubernetes_tpu.client.record import EventRecorder
from kubernetes_tpu.models import explain
from kubernetes_tpu.models.batch_solver import decisions_to_names, solve
from kubernetes_tpu.models.incremental import IncrementalEncoder
from kubernetes_tpu.models.oracle import explain_serial
from kubernetes_tpu.models.snapshot import encode_snapshot
from kubernetes_tpu.addons.monitoring import (
    FlightAggregator,
    default_churn_rules,
)
from kubernetes_tpu.scheduler.driver import ConfigFactory, PodBackoff
from kubernetes_tpu.scheduler.tpu_batch import BatchScheduler
from kubernetes_tpu.util import metrics


def mknode(i, cpu="1", mem="8Gi", labels=None):
    return api.Node(
        metadata=api.ObjectMeta(name=f"n{i:03d}", labels=labels or {}),
        spec=api.NodeSpec(capacity={"cpu": Quantity(cpu),
                                    "memory": Quantity(mem)}))


def mkpod(name, mcpu=500, host="", prio=0, can=True, port=0, ns="default",
          sel=None, pin="", pd=""):
    ports = [api.ContainerPort(container_port=80, host_port=port)] \
        if port else []
    vols = [api.Volume(name="v", source=api.VolumeSource(
        gce_persistent_disk=api.GCEPersistentDiskVolumeSource(
            pd_name=pd)))] if pd else []
    return api.Pod(
        metadata=api.ObjectMeta(name=name, namespace=ns, uid=f"uid-{name}"),
        spec=api.PodSpec(
            containers=[api.Container(
                name="c", image="i", ports=ports,
                resources=api.ResourceRequirements(limits={
                    "cpu": Quantity(f"{mcpu}m"),
                    "memory": Quantity("64Mi")}))],
            priority=prio, node_selector=sel or {}, host=pin, volumes=vols,
            preemption_policy=("" if can else api.PreemptNever)),
        status=api.PodStatus(host=host))


def check_identity(nodes, existing, pending, encoder=None):
    """Solve + explain the wave both ways; assert decisions AND
    per-reason counts match. Returns the dense diagnoses."""
    if encoder is not None:
        snap = encoder.encode(nodes, existing, pending)
    else:
        snap = encode_snapshot(nodes, existing, pending)
    chosen, scores = solve(snap)
    diags = explain.explain_wave(snap, chosen, scores)
    dec, sdiags = explain_serial(nodes, existing, pending)
    assert decisions_to_names(snap, chosen) == dec
    for j in range(len(pending)):
        d, s = diags.get(j), sdiags[j]
        assert (d is None) == (s is None), (j, d, s)
        if d is not None:
            assert d.counts == s.counts, (j, d.counts, s.counts)
            assert d.preempt == s.preempt, (j, d, s)
            assert d.n_nodes == s.n_nodes
            # attribution is disjoint: one reason per eliminated node,
            # and an unschedulable pod has zero feasible nodes
            assert sum(d.counts.values()) == d.n_nodes
    return diags


class TestOracleCountIdentity:
    def test_full_cluster_insufficient(self):
        nodes = [mknode(i) for i in range(4)]
        existing = [mkpod(f"e-{i}-{j}", host=f"n{i:03d}")
                    for i in range(4) for j in range(2)]
        diags = check_identity(nodes, existing, [mkpod("p1"), mkpod("p2")])
        assert diags[0].counts == {"Insufficient cpu": 4}
        assert diags[1].counts == {"Insufficient cpu": 4}

    def test_tied_filters_attribute_serial_short_circuit_order(self):
        # the node conflicts on the host port AND lacks cpu: the serial
        # scheduler's find_nodes_that_fit short-circuits on PodFitsPorts
        # first, so the count lands there
        nodes = [mknode(0)]
        existing = [mkpod("e", host="n000", mcpu=800, port=80)]
        diags = check_identity(nodes, existing,
                               [mkpod("p", mcpu=500, port=80)])
        assert diags[0].counts == {"Port conflict": 1}

    def test_selector_host_and_pd_reasons(self):
        nodes = [mknode(i, labels={"zone": "a" if i < 2 else "b"})
                 for i in range(4)]
        existing = [mkpod("e", host="n000", pd="disk-1", mcpu=100)]
        diags = check_identity(nodes, existing, [
            mkpod("sel", sel={"zone": "c"}, mcpu=100),
            mkpod("pin", pin="ghost", mcpu=100),
            mkpod("pd", pd="disk-1", mcpu=100, sel={"zone": "a"}),
        ])
        assert diags[0].counts == {"Node selector mismatch": 4}
        assert diags[1].counts == {"Host mismatch": 4}
        # PD conflict on n000; the other zone-a node is feasible, so the
        # pd pod actually places — only the first two stay unschedulable
        assert 2 not in diags

    def test_overcommitted_node(self):
        # the existing pod never fit (greedy pre-exceeded): per-dim
        # headroom looks fine for a tiny pod, but the node fails
        # CheckPodsExceedingCapacity — attributed Node overcommitted
        nodes = [mknode(0, cpu="1")]
        existing = [mkpod("big-e", host="n000", mcpu=1500)]
        diags = check_identity(nodes, existing, [mkpod("tiny", mcpu=100)])
        assert diags[0].counts == {"Node overcommitted": 1}

    def test_preemption_ineligible_reasons(self):
        nodes = [mknode(i) for i in range(3)]
        existing = [mkpod(f"low-{i}-{j}", host=f"n{i:03d}", prio=10)
                    for i in range(3) for j in range(2)]
        diags = check_identity(nodes, existing, [
            mkpod("never", prio=100, can=False),
            mkpod("big", mcpu=2000, prio=100),
        ])
        assert diags[0].preempt == "Never"
        assert diags[1].preempt == "no_prefix"

    def test_post_eviction_carry(self):
        # the first pod places VIA PREEMPTION; the second is diagnosed
        # against the post-eviction planes (freed capacity subtracted)
        nodes = [mknode(i) for i in range(2)]
        existing = [mkpod(f"low-{i}-{j}", host=f"n{i:03d}", prio=10)
                    for i in range(2) for j in range(2)]
        diags = check_identity(nodes, existing, [
            mkpod("hi", mcpu=900, prio=100),
            mkpod("p2", mcpu=900, prio=10),
        ])
        assert 0 not in diags          # placed (by eviction)
        assert diags[1].counts == {"Insufficient cpu": 2}

    def test_legacy_wave_has_no_preempt_state(self):
        # every pod at the resident priority floor: the emit gate ships
        # B == 0 and the diagnosis carries no preempt suffix
        nodes = [mknode(0)]
        existing = [mkpod("e", host="n000", prio=0)]
        diags = check_identity(nodes, existing, [mkpod("p", mcpu=800)])
        assert diags[0].preempt == ""

    def test_empty_cluster_no_nodes(self):
        # the serial scheduler fails the wave before any predicate runs;
        # the dense twin reports an empty decomposition over 0 nodes
        snap = encode_snapshot([], [], [mkpod("p")])
        diags = explain.explain_wave(snap, [-1], [-1])
        dec, sdiags = explain_serial([], [], [mkpod("p")])
        assert dec == [None]
        assert diags[0].counts == sdiags[0].counts == {}
        assert diags[0].n_nodes == 0

    def test_fuzz_identity_full_and_incremental(self):
        rng = random.Random(11)
        for trial in range(12):
            N = rng.randint(1, 6)
            nodes = [mknode(i, cpu=rng.choice(["1", "2"]),
                            labels={"zone": rng.choice(["a", "b"])})
                     for i in range(N)]
            existing = [
                mkpod(f"e-{trial}-{i}-{j}", host=f"n{i:03d}",
                      mcpu=rng.choice([200, 500, 800]),
                      prio=rng.choice([0, 10, 50]),
                      port=rng.choice([0, 0, 80]),
                      pd=rng.choice(["", "", f"pd-{i}"]))
                for i in range(N) for j in range(rng.randint(0, 3))]
            pending = [
                mkpod(f"p-{trial}-{k}",
                      mcpu=rng.choice([100, 600, 1200, 2500]),
                      prio=rng.choice([0, 20, 100]),
                      can=rng.random() > 0.3,
                      port=rng.choice([0, 0, 80]),
                      sel=rng.choice([None, None, {"zone": "a"},
                                      {"zone": "z"}]),
                      pd=rng.choice(["", "", f"pd-{rng.randrange(N)}"]),
                      pin=rng.choice(["", "", f"n{rng.randrange(N):03d}",
                                      "ghost"]))
                for k in range(rng.randint(1, 6))]
            check_identity(nodes, existing, pending)
            check_identity(nodes, existing, pending,
                           encoder=IncrementalEncoder())


class TestMessageGoldens:
    def test_topk_line(self):
        d = explain.PodDiagnosis(10000, {"Insufficient cpu": 9988,
                                         "Port conflict": 12})
        assert explain.format_message(d) == \
            "0/10000 nodes available: 9988 Insufficient cpu, " \
            "12 Port conflict"

    def test_tie_breaks_by_reason_name_and_other_bucket(self):
        d = explain.PodDiagnosis(15, {"Port conflict": 5, "PD conflict": 5,
                                      "Host mismatch": 2,
                                      "Insufficient cpu": 2,
                                      "Node selector mismatch": 1})
        assert explain.format_message(d, top_k=2) == \
            "0/15 nodes available: 5 PD conflict, 5 Port conflict, 5 other"

    def test_preempt_suffixes(self):
        d = explain.PodDiagnosis(3, {"Insufficient cpu": 3}, "Never")
        assert explain.format_message(d) == \
            "0/3 nodes available: 3 Insufficient cpu; preemption not " \
            "attempted (preemptionPolicy: Never)"
        d = explain.PodDiagnosis(3, {"Insufficient cpu": 3}, "no_prefix")
        assert explain.format_message(d).endswith(
            "; preemption would not help (no lower-priority victim set "
            "frees enough)")

    def test_no_nodes_line(self):
        assert explain.format_message(explain.PodDiagnosis(0, {})) == \
            "0/0 nodes available"

    def test_dominant_reason(self):
        d = explain.PodDiagnosis(10, {"Port conflict": 4,
                                      "Insufficient cpu": 6})
        assert explain.dominant_reason(d) == "Insufficient cpu"
        assert explain.dominant_reason(explain.PodDiagnosis(0, {})) == \
            explain.REASON_UNEXPLAINED


def _solved_wave(n_nodes=2):
    """A tiny solved wave with one unschedulable pod."""
    nodes = [mknode(i) for i in range(n_nodes)]
    existing = [mkpod(f"e{i}", host=f"n{i:03d}", mcpu=900)
                for i in range(n_nodes)]
    pending = [mkpod("p", mcpu=500)]
    snap = encode_snapshot(nodes, existing, pending)
    chosen, scores = solve(snap)
    assert int(chosen[0]) < 0
    return snap, chosen, scores


class TestOffHotPathGuard:
    def test_rate_limit_declines_and_counts_unexplained(self):
        mx = metrics.explain_metrics()
        ex = explain.Explainer(qps=0.0001, burst=1)
        snap, chosen, scores = _solved_wave()
        pods0 = mx.pods.value()
        unexp0 = mx.reasons.value(explain.REASON_UNEXPLAINED)
        skip0 = mx.skipped.value("rate_limited")
        inv0 = mx.invocations.value()
        assert ex.diagnose_wave(snap, chosen, scores)   # burst token
        assert ex.diagnose_wave(snap, chosen, scores) == {}  # declined
        assert mx.pods.value() - pods0 == 2
        assert mx.skipped.value("rate_limited") - skip0 == 1
        assert mx.reasons.value(explain.REASON_UNEXPLAINED) - unexp0 == 1
        assert mx.invocations.value() - inv0 == 1

    def test_rate_limit_refills_with_time(self, monkeypatch):
        # the bucket is the only gate: a declined wave is admitted again
        # once 1/qps seconds have passed, and never holds more than burst
        now = [1000.0]
        monkeypatch.setattr(explain, "time", types.SimpleNamespace(
            monotonic=lambda: now[0], thread_time=time.thread_time))
        mx = metrics.explain_metrics()
        ex = explain.Explainer(qps=2.0, burst=2)
        snap, chosen, scores = _solved_wave()
        skip0 = mx.skipped.value("rate_limited")
        assert ex.diagnose_wave(snap, chosen, scores)
        assert ex.diagnose_wave(snap, chosen, scores)
        assert ex.diagnose_wave(snap, chosen, scores) == {}
        now[0] += 0.5                       # one token at 2/s
        assert ex.diagnose_wave(snap, chosen, scores)
        assert ex.diagnose_wave(snap, chosen, scores) == {}
        now[0] += 3600.0                    # refills to burst, not past it
        assert ex.diagnose_wave(snap, chosen, scores)
        assert ex.diagnose_wave(snap, chosen, scores)
        assert ex.diagnose_wave(snap, chosen, scores) == {}
        assert mx.skipped.value("rate_limited") - skip0 == 3

    def test_schedulable_wave_is_free(self):
        # no unschedulable rows: diagnose_wave returns without touching
        # the rate limiter or invoking the kernel
        mx = metrics.explain_metrics()
        ex = explain.Explainer(qps=0.0001, burst=0)   # would decline
        nodes = [mknode(0, cpu="8")]
        pending = [mkpod("p")]
        snap = encode_snapshot(nodes, [], pending)
        chosen, scores = solve(snap)
        inv0, pods0 = mx.invocations.value(), mx.pods.value()
        assert ex.diagnose_wave(snap, chosen, scores) == {}
        assert mx.invocations.value() == inv0
        assert mx.pods.value() == pods0

    def test_internal_error_keeps_reason_sums(self, monkeypatch):
        # any failure AFTER the pods counter advanced must land in a
        # skip bucket too, or the by-reason family stops summing to the
        # pods family forever
        mx = metrics.explain_metrics()
        ex = explain.Explainer()
        snap, chosen, scores = _solved_wave()
        monkeypatch.setattr(explain, "explain_wave",
                            lambda *a, **kw: (_ for _ in ()).throw(
                                RuntimeError("boom")))
        pods0 = mx.pods.value()
        unexp0 = mx.reasons.value(explain.REASON_UNEXPLAINED)
        err0 = mx.skipped.value("error")
        assert ex.diagnose_wave(snap, chosen, scores) == {}
        assert mx.pods.value() - pods0 == 1
        assert mx.skipped.value("error") - err0 == 1
        assert mx.reasons.value(explain.REASON_UNEXPLAINED) - unexp0 == 1

    def test_forced_requeue_rows_counted_unexplained(self):
        # the full-encoder preemption path fails pods whose chosen stays
        # >= 0 (host forced to None): the caller's n_unsched covers them
        # — counted in the pods family, bucketed unexplained
        mx = metrics.explain_metrics()
        ex = explain.Explainer()
        snap, chosen, scores = _solved_wave()
        pods0 = mx.pods.value()
        unexp0 = mx.reasons.value(explain.REASON_UNEXPLAINED)
        msgs = ex.diagnose_wave(snap, chosen, scores, n_unsched=3)
        assert len(msgs) == 1                       # the real -1 row
        assert mx.pods.value() - pods0 == 3
        assert mx.reasons.value(explain.REASON_UNEXPLAINED) - unexp0 == 2

    def test_unsupported_wave_skipped(self):
        mx = metrics.explain_metrics()
        ex = explain.Explainer()
        snap, chosen, scores = _solved_wave()
        snap.pod_rid[0] = 3          # fake a gang member: has_gangs True
        skip0 = mx.skipped.value("unsupported")
        assert ex.diagnose_wave(snap, chosen, scores) == {}
        assert mx.skipped.value("unsupported") - skip0 == 1


def _wait(pred, timeout=15.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.05)
    return False


class TestSchedulerEndToEnd:
    def test_event_carries_breakdown(self):
        m = Master()
        client = Client(InProcessTransport(m))
        client.nodes().create(mknode(0, cpu="1"))
        client.pods().create(mkpod("resident", host="n000", mcpu=900))
        recorder = EventRecorder(client, api.EventSource(component="sched"))
        factory = ConfigFactory(client)
        factory.backoff = PodBackoff(initial=0.05, max_duration=0.2)
        config = factory.create(recorder=recorder)
        sched = BatchScheduler(config, factory, client, wave_size=8,
                               wave_linger_s=0.05)
        sched.run()
        try:
            time.sleep(0.3)
            client.pods().create(mkpod("wont-fit", mcpu=500))
            assert _wait(lambda: any(
                ev.reason == "FailedScheduling"
                and "nodes available" in ev.message
                for ev in client.events("default").list().items), 10.0), \
                [ev.message for ev in client.events("default").list().items]
        finally:
            sched.stop()
            factory.stop()
        ev = next(ev for ev in client.events("default").list().items
                  if ev.reason == "FailedScheduling"
                  and "nodes available" in ev.message)
        assert ev.message == "0/1 nodes available: 1 Insufficient cpu"
        # kubectl-visible with zero new plumbing: describe pod renders
        # the breakdown through the existing events table
        from kubernetes_tpu.kubectl.describe import describe
        text = describe(client, "pods", "default", "wont-fit")
        assert "0/1 nodes available: 1 Insufficient cpu" in text, text


def _ns(s):
    return int(s * 1e9)


def _payload(pid, service, series, t_ns):
    return {"armed": True, "pid": pid, "service": service,
            "period_s": 1.0, "t_ns": t_ns,
            "series": {k: {"type": typ, "samples": pts}
                       for k, (typ, pts) in series.items()}}


class TestFailedSchedulingBurstSLO:
    def test_rule_is_in_default_churn_set(self):
        names = [r.name for r in default_churn_rules()]
        assert "failed_scheduling_burst" in names

    def test_fire_and_resolve_transitions(self):
        rule = next(r for r in default_churn_rules()
                    if r.name == "failed_scheduling_burst")
        agg = FlightAggregator([], rules=[rule], fetch=None)
        agg.set_active(True)
        # a burst: 100 unschedulable/s sustained past for_s
        for t in range(0, 16, 2):
            agg.ingest(_payload(1, "scheduler", {
                "scheduler_unschedulable_pods_total":
                    ("counter", [[_ns(t), 100.0 * t]])}, _ns(t)))
            agg.evaluate(_ns(t))
        firing = [tr for tr in agg.alarms() if tr["state"] == "firing"]
        assert [tr["rule"] for tr in firing] == ["failed_scheduling_burst"]
        # recovery: the counter flattens, the rate falls under the
        # threshold, the alarm resolves (one transition each way)
        for t in range(16, 60, 2):
            agg.ingest(_payload(1, "scheduler", {
                "scheduler_unschedulable_pods_total":
                    ("counter", [[_ns(t), 1500.0]])}, _ns(t)))
            agg.evaluate(_ns(t))
        states = [tr["state"] for tr in agg.alarms()
                  if tr["rule"] == "failed_scheduling_burst"]
        assert states == ["firing", "resolved"]

    def test_quiet_when_inactive(self):
        rule = next(r for r in default_churn_rules()
                    if r.name == "failed_scheduling_burst")
        agg = FlightAggregator([], rules=[rule], fetch=None)
        agg.set_active(False)      # load window closed: active_only gates
        for t in range(0, 16, 2):
            agg.ingest(_payload(1, "scheduler", {
                "scheduler_unschedulable_pods_total":
                    ("counter", [[_ns(t), 100.0 * t]])}, _ns(t)))
            agg.evaluate(_ns(t))
        assert agg.alarms() == []
