"""kube-preempt — PriorityClass + batched preemption as a dense solve.

The contract under test (docs/design/batch-solver.md preemption section):

- batched decisions AND victim sets bit-identical to the preempt_serial
  oracle across full / empty / tied clusters (fuzzed + pinned cases);
- never-evict-equal-or-higher is structural (invariant over every fuzz
  trial), PreemptionPolicy=Never pods never place via eviction;
- legacy waves (no priority diversity) compile the exact pre-preemption
  program (the emit gate: B == 0);
- the atomic evict+bind commit: all victims deleted AND the pod bound, or
  a per-item 409 with NOTHING applied (CAS loss / victim uid change);
- the incremental encoder's evictable planes stay exact vs the
  derive_evict_planes from-scratch twin at O(1) writes per delta;
- the whole path live: a full cluster, a high-priority pod, an atomic
  evict+bind through Master, the victims' DELETE watch events.
"""

import random
import time

import numpy as np
import pytest

from kubernetes_tpu.api import errors, types as api
from kubernetes_tpu.api.quantity import Quantity
from kubernetes_tpu.apiserver.master import Master
from kubernetes_tpu.client.client import Client, InProcessTransport
from kubernetes_tpu.models import preempt as preempt_mod
from kubernetes_tpu.models.batch_solver import (
    decisions_to_names,
    snapshot_to_host_inputs,
    solve,
)
from kubernetes_tpu.models.incremental import IncrementalEncoder
from kubernetes_tpu.models.oracle import preempt_serial, solve_serial
from kubernetes_tpu.models.snapshot import encode_snapshot
from kubernetes_tpu.registry.generic import Context


def mknode(i, cpu="1", mem="8Gi"):
    return api.Node(
        metadata=api.ObjectMeta(name=f"n{i:03d}"),
        spec=api.NodeSpec(capacity={"cpu": Quantity(cpu),
                                    "memory": Quantity(mem)}))


def mkpod(name, mcpu=500, host="", prio=0, can=True, port=0, ns="default"):
    ports = [api.ContainerPort(container_port=80, host_port=port)] \
        if port else []
    return api.Pod(
        metadata=api.ObjectMeta(name=name, namespace=ns, uid=f"uid-{name}"),
        spec=api.PodSpec(
            containers=[api.Container(
                name="c", image="i", ports=ports,
                resources=api.ResourceRequirements(limits={
                    "cpu": Quantity(f"{mcpu}m"),
                    "memory": Quantity("64Mi")}))],
            priority=prio,
            preemption_policy=("" if can else api.PreemptNever)),
        status=api.PodStatus(host=host))


def batch_with_victims(nodes, existing, pending, encoder=None):
    """Batched decisions + victim sets for one wave (either encoder)."""
    if encoder is not None:
        snap = encoder.encode(nodes, existing, pending)
        node_pods = encoder.resident_on
        resident = None
    else:
        snap = encode_snapshot(nodes, existing, pending)
        node_index = {n.metadata.name: i for i, n in enumerate(nodes)}
        resident = preempt_mod.resident_from_pods(existing, node_index)
        node_pods = None
    chosen, scores = solve(snap)
    names = decisions_to_names(snap, chosen)
    victims = preempt_mod.assign_victims(
        chosen, scores, snap.band_prio, resident=resident,
        n_pods=len(pending), node_pods=node_pods)
    return names, victims, snap, scores


def norm(victims):
    return [sorted(v.uid for v in (x or [])) or None for x in victims]


class TestOracleBitIdentity:
    def test_full_cluster_preempts_lowest_band(self):
        nodes = [mknode(i) for i in range(4)]
        existing = [mkpod(f"low-{i}-{j}", host=f"n{i:03d}", prio=10)
                    for i in range(4) for j in range(2)]
        pending = [mkpod("high", prio=1000)]
        names, victims, snap, scores = batch_with_victims(
            nodes, existing, pending)
        s_names, s_victims = preempt_serial(nodes, existing, pending)
        assert names == s_names and names[0] is not None
        assert norm(victims) == norm(s_victims)
        assert victims[0] and all(v.priority == 10 for v in victims[0])
        assert preempt_mod.is_preempt_score(int(scores[0]))

    def test_empty_cluster_never_preempts(self):
        nodes = [mknode(i) for i in range(3)]
        pending = [mkpod("high", prio=1000), mkpod("low", prio=0)]
        names, victims, snap, _ = batch_with_victims(nodes, [], pending)
        s_names, s_victims = preempt_serial(nodes, [], pending)
        assert names == s_names
        assert all(v is None for v in victims)
        # no resident pods -> no bands -> the legacy program compiled
        assert snap.band_prio.shape[0] == 0

    def test_tied_clusters_tie_break_matches(self):
        # every node identical: the FNV tie-break must pick the same
        # node (and the same victims) on both paths
        nodes = [mknode(i) for i in range(8)]
        existing = [mkpod(f"e-{i}", mcpu=1000, host=f"n{i:03d}", prio=7)
                    for i in range(8)]
        pending = [mkpod(f"h-{k}", mcpu=1000, prio=99) for k in range(5)]
        names, victims, _, _ = batch_with_victims(nodes, existing, pending)
        s_names, s_victims = preempt_serial(nodes, existing, pending)
        assert names == s_names
        assert norm(victims) == norm(s_victims)
        assert all(n is not None for n in names)

    def test_lowest_sufficient_band_prefix_is_chosen(self):
        # one node, two bands: a preemptor that fits by clearing only the
        # lower band must not touch the upper one
        nodes = [mknode(0, cpu="1")]
        existing = [mkpod("b100", mcpu=500, host="n000", prio=100),
                    mkpod("b200", mcpu=500, host="n000", prio=200)]
        pending = [mkpod("high", mcpu=500, prio=1000)]
        names, victims, _, _ = batch_with_victims(nodes, existing, pending)
        s_names, s_victims = preempt_serial(nodes, existing, pending)
        assert names == s_names == ["n000"]
        assert norm(victims) == norm(s_victims) == [["uid-b100"]]

    def test_min_victim_cost_across_nodes(self):
        # n0 holds two small low pods, n1 one big low pod: evicting from
        # n1 costs fewer victims and must win
        nodes = [mknode(0, cpu="1"), mknode(1, cpu="1")]
        existing = [mkpod("a1", mcpu=500, host="n000", prio=5),
                    mkpod("a2", mcpu=500, host="n000", prio=5),
                    mkpod("b1", mcpu=1000, host="n001", prio=5)]
        pending = [mkpod("high", mcpu=1000, prio=50)]
        names, victims, _, _ = batch_with_victims(nodes, existing, pending)
        s_names, s_victims = preempt_serial(nodes, existing, pending)
        assert names == s_names == ["n001"]
        assert norm(victims) == norm(s_victims) == [["uid-b1"]]

    def test_fuzz_decisions_and_victims(self):
        random.seed(1234)
        for _ in range(15):
            N = random.randint(2, 6)
            nodes = [mknode(i, cpu=random.choice(["1", "2"]))
                     for i in range(N)]
            existing = [
                mkpod(f"e-{i}-{j}", random.choice([200, 300, 500]),
                      host=f"n{i:03d}", prio=random.choice([0, 5, 10, 50]),
                      port=random.choice([0, 0, 0, 7070]))
                for i in range(N) for j in range(random.randint(0, 4))]
            pending = [
                mkpod(f"p-{k}", random.choice([300, 500, 800, 1500]),
                      prio=random.choice([0, 10, 100, 1000]),
                      can=random.random() > 0.2,
                      port=random.choice([0, 0, 7070]))
                for k in range(random.randint(1, 6))]
            names, victims, _, _ = batch_with_victims(
                nodes, existing, pending)
            s_names, s_victims = preempt_serial(nodes, existing, pending)
            assert names == s_names
            assert norm(victims) == norm(s_victims)
            # structural invariant: never evict equal-or-higher
            prio_of = {p.metadata.uid: api.pod_priority(p)
                       for p in existing}
            for p, v in zip(pending, victims):
                if v:
                    assert all(prio_of[x.uid] < api.pod_priority(p)
                               for x in v)
                    assert api.pod_can_preempt(p)


class TestInvariants:
    def test_preemption_policy_never_honored(self):
        nodes = [mknode(0)]
        existing = [mkpod(f"low-{j}", host="n000", prio=1)
                    for j in range(2)]
        pending = [mkpod("never", prio=1000, can=False)]
        names, victims, _, _ = batch_with_victims(nodes, existing, pending)
        s_names, _sv = preempt_serial(nodes, existing, pending)
        assert names == s_names == [None]
        assert victims == [None]

    def test_equal_priority_never_evicted(self):
        nodes = [mknode(0)]
        existing = [mkpod(f"peer-{j}", host="n000", prio=100)
                    for j in range(2)]
        pending = [mkpod("equal", prio=100), mkpod("below", prio=50)]
        names, victims, _, _ = batch_with_victims(nodes, existing, pending)
        s_names, _ = preempt_serial(nodes, existing, pending)
        assert names == s_names == [None, None]

    def test_legacy_wave_compiles_without_bands(self):
        # no priority diversity -> the emit gate keeps B == 0 and the
        # decisions equal the pre-preemption oracle exactly
        nodes = [mknode(i, cpu="4") for i in range(3)]
        existing = [mkpod(f"e-{i}", host=f"n{i:03d}") for i in range(3)]
        pending = [mkpod(f"p-{k}", mcpu=300) for k in range(4)]
        snap = encode_snapshot(nodes, existing, pending)
        assert snap.band_prio.shape[0] == 0
        host = snapshot_to_host_inputs(snap)
        assert host.evict_cap.shape[1] == 0
        chosen, scores = solve(snap)
        assert decisions_to_names(snap, chosen) == \
            solve_serial(nodes, existing, pending)
        assert all(int(s) >= 0 for s in scores[:len(pending)])

    def test_within_wave_placements_never_evicted(self):
        # pod A (prio 500) places normally; pod B (prio 1000) must evict
        # the wave-start resident, never A
        nodes = [mknode(0, cpu="1")]
        existing = [mkpod("old", mcpu=500, host="n000", prio=10)]
        pending = [mkpod("a", mcpu=500, prio=500),
                   mkpod("b", mcpu=1000, prio=1000)]
        names, victims, _, _ = batch_with_victims(nodes, existing, pending)
        s_names, s_victims = preempt_serial(nodes, existing, pending)
        assert names == s_names
        for v in victims:
            if v:
                assert all(x.uid != "uid-a" for x in v)
        assert norm(victims) == norm(s_victims)


class TestIncrementalEvictPlanes:
    def test_incremental_matches_full_encoder_decisions(self):
        nodes = [mknode(i) for i in range(4)]
        existing = [mkpod(f"low-{i}-{j}", host=f"n{i:03d}", prio=10)
                    for i in range(4) for j in range(2)]
        pending = [mkpod("h1", prio=1000), mkpod("h2", prio=1000)]
        enc = IncrementalEncoder()
        n_i, v_i, _, _ = batch_with_victims(nodes, existing, pending,
                                            encoder=enc)
        n_f, v_f, _, _ = batch_with_victims(nodes, existing, pending)
        assert n_i == n_f
        assert norm(v_i) == norm(v_f)

    def test_evict_planes_exact_vs_derive_twin_o1_writes(self):
        nodes = [mknode(i, cpu="4") for i in range(3)]
        existing = [mkpod(f"e-{i}-{j}", host=f"n{i:03d}",
                          prio=10 * (j + 1))
                    for i in range(3) for j in range(2)]
        enc = IncrementalEncoder()
        enc.encode(nodes, existing, [mkpod("seed", prio=1000)])
        base_writes = enc.op_counts["evict_writes"]
        # one add + one remove = exactly 2 single-element plane updates
        newpod = mkpod("new", host="n001", prio=30)
        snap = enc.encode_delta(nodes, [newpod], [existing[0]],
                                [mkpod("pend", prio=1000)])
        assert snap is not None
        assert enc.op_counts["evict_writes"] - base_writes == 2
        assert enc.op_counts["node_rebuilds"] == 1  # no extra rebuilds
        # exactness vs the from-scratch twin over the surviving pods
        resident = existing[1:] + [newpod]
        e_host = np.array([int(p.status.host[1:]) for p in resident])
        e_prio = np.array([api.pod_priority(p) for p in resident])
        R = snap.evict_cap.shape[2]
        rix = {name: r for r, name in enumerate(snap.resource_names)}
        e_req = np.zeros((len(resident), R), np.int64)
        for k, p in enumerate(resident):
            e_req[k, rix["cpu"]] = 500
            e_req[k, rix["memory"]] = 64 << 20
        want_cap, want_cnt = preempt_mod.derive_evict_planes(
            e_host, e_prio, e_req, snap.band_prio, len(nodes))
        assert np.array_equal(want_cap, snap.evict_cap)
        assert np.array_equal(want_cnt, snap.evict_cnt)

    def test_a_pod_gone_from_existing_rolls_evict_planes_back_exactly(self):
        # the full diff-walk: a pod that left ``existing_pods`` is removed
        # by encode(), and the evict planes equal a fresh encoder's
        nodes = [mknode(i) for i in range(2)]
        existing = [mkpod("e-0", host="n000", prio=10)]
        enc = IncrementalEncoder()
        snap0 = enc.encode(nodes, existing, [mkpod("p", prio=100)])
        gone = mkpod("gone", host="n001", prio=20)
        enc.encode_delta(nodes, [gone], [], [mkpod("p2", prio=100)])
        assert gone.metadata.uid in enc._pods
        snap2 = enc.encode(nodes, existing, [mkpod("p3", prio=100)])
        assert gone.metadata.uid not in enc._pods
        fresh = IncrementalEncoder().encode(nodes, existing,
                                            [mkpod("p3", prio=100)])
        for snap in (snap0, fresh):
            assert np.array_equal(snap.evict_cnt, snap2.evict_cnt)
            assert np.array_equal(snap.evict_cap, snap2.evict_cap)


class TestAtomicEvictBind:
    def _master(self):
        m = Master()
        ctx = Context(namespace="default")
        return m, ctx

    def _create(self, m, name, host=""):
        pod = api.Pod(metadata=api.ObjectMeta(name=name,
                                              namespace="default"),
                      spec=api.PodSpec(containers=[
                          api.Container(name="c", image="i")]))
        out = m.dispatch("create", "pods", namespace="default", body=pod)
        if host:
            m.bindings.create(Context(namespace="default"), api.Binding(
                metadata=api.ObjectMeta(name=name, namespace="default"),
                pod_name=name, host=host))
            out = m.pods.get(Context(namespace="default"), name)
        return out

    def test_evict_and_bind_commit_together(self):
        m, ctx = self._master()
        v = self._create(m, "victim", host="n1")
        self._create(m, "preemptor")
        res = m.bind_batch("default", api.BindingList(items=[api.Binding(
            metadata=api.ObjectMeta(name="preemptor",
                                    namespace="default"),
            pod_name="preemptor", host="n1",
            victims=[api.ObjectReference(kind="Pod", namespace="default",
                                         name="victim",
                                         uid=v.metadata.uid)])]))
        assert not res.items[0].error
        with pytest.raises(errors.StatusError):
            m.pods.get(ctx, "victim")
        assert m.pods.get(ctx, "preemptor").spec.host == "n1"

    def test_victim_uid_change_is_409_and_nothing_applies(self):
        m, ctx = self._master()
        self._create(m, "victim", host="n1")
        self._create(m, "preemptor")
        res = m.bind_batch("default", api.BindingList(items=[api.Binding(
            metadata=api.ObjectMeta(name="preemptor",
                                    namespace="default"),
            pod_name="preemptor", host="n1",
            victims=[api.ObjectReference(kind="Pod", namespace="default",
                                         name="victim",
                                         uid="stale-uid")])]))
        assert res.items[0].code == 409
        # NOTHING applied: victim survives, preemptor stays unbound
        assert m.pods.get(ctx, "victim").metadata.name == "victim"
        assert m.pods.get(ctx, "preemptor").spec.host == ""

    def test_pod_cas_loss_is_409_and_victims_survive(self):
        m, ctx = self._master()
        v = self._create(m, "victim", host="n1")
        self._create(m, "preemptor", host="n9")  # already bound: CAS loses
        res = m.bind_batch("default", api.BindingList(items=[api.Binding(
            metadata=api.ObjectMeta(name="preemptor",
                                    namespace="default"),
            pod_name="preemptor", host="n1",
            victims=[api.ObjectReference(kind="Pod", namespace="default",
                                         name="victim",
                                         uid=v.metadata.uid)])]))
        assert res.items[0].code == 409
        assert m.pods.get(ctx, "victim").metadata.name == "victim"
        assert m.pods.get(ctx, "preemptor").spec.host == "n9"

    def test_absent_victim_counts_as_evicted(self):
        m, ctx = self._master()
        self._create(m, "preemptor")
        res = m.bind_batch("default", api.BindingList(items=[api.Binding(
            metadata=api.ObjectMeta(name="preemptor",
                                    namespace="default"),
            pod_name="preemptor", host="n1",
            victims=[api.ObjectReference(kind="Pod", namespace="default",
                                         name="already-gone", uid="x")])]))
        assert not res.items[0].error
        assert m.pods.get(ctx, "preemptor").spec.host == "n1"

    def test_victims_require_pod_delete_authorization(self):
        """Binding create rights are NOT pod delete rights: an evict+bind
        item runs a DELETE authorization per victim namespace — including
        the request's own — on both the batch and per-pod binding paths."""
        from kubernetes_tpu.apiserver.master import MasterConfig

        class NoPodDeletes:
            def authorize(self, user, attrs):
                if attrs.resource == "pods" and attrs.operation == "DELETE":
                    raise errors.new_forbidden("pods", attrs.namespace,
                                               "no pod deletes for you")

        m = Master(MasterConfig(authorizer=NoPodDeletes()))
        ctx = Context(namespace="default")
        pod = api.Pod(metadata=api.ObjectMeta(name="victim",
                                              namespace="default"),
                      spec=api.PodSpec(containers=[
                          api.Container(name="c", image="i")]))
        v = m.dispatch("create", "pods", namespace="default", body=pod)
        binding = api.Binding(
            metadata=api.ObjectMeta(name="p", namespace="default"),
            pod_name="p", host="n1",
            victims=[api.ObjectReference(kind="Pod", namespace="default",
                                         name="victim",
                                         uid=v.metadata.uid)])
        with pytest.raises(errors.StatusError) as ei:
            m.bind_batch("default", api.BindingList(items=[binding]))
        assert ei.value.status.code == 403
        with pytest.raises(errors.StatusError) as ei:
            m.dispatch("create", "pods", namespace="default", name="p",
                       subresource="binding", body=binding)
        assert ei.value.status.code == 403
        # the victim survives both refused attempts
        assert m.pods.get(ctx, "victim").metadata.name == "victim"
        # a victim-free binding through the same authorizer still works
        m.bind_batch("default", api.BindingList(items=[api.Binding(
            metadata=api.ObjectMeta(name="victim", namespace="default"),
            pod_name="victim", host="n1")]))
        assert m.pods.get(ctx, "victim").spec.host == "n1"

    def test_victim_delete_emits_watch_event(self):
        m, ctx = self._master()
        v = self._create(m, "victim", host="n1")
        self._create(m, "preemptor")
        w = m.pods.watch(ctx)
        try:
            m.bind_batch("default", api.BindingList(items=[api.Binding(
                metadata=api.ObjectMeta(name="preemptor",
                                        namespace="default"),
                pod_name="preemptor", host="n1",
                victims=[api.ObjectReference(
                    kind="Pod", namespace="default", name="victim",
                    uid=v.metadata.uid)])]))
            seen = []
            deadline = time.monotonic() + 5
            it = iter(w)
            while time.monotonic() < deadline and len(seen) < 2:
                seen.append(next(it))
            kinds = {(ev.type, ev.object.metadata.name) for ev in seen}
            # the kubelet-teardown trigger: the victim's DELETE frame,
            # plus the preemptor's bind MODIFY — one transaction, two
            # ordered events
            assert ("DELETED", "victim") in kinds
        finally:
            w.stop()


class TestPriorityClassAPI:
    def test_admission_paths(self):
        m = Master()
        ctx = Context()
        m.priorityclasses.create(ctx, api.PriorityClass(
            metadata=api.ObjectMeta(name="high"), value=1000,
            preemption_policy=api.PreemptNever))
        m.priorityclasses.create(ctx, api.PriorityClass(
            metadata=api.ObjectMeta(name="low"), value=100,
            global_default=True))

        def fresh(name, cls="", prio=None):
            p = mkpod(name, ns="default")
            p.spec.priority = prio
            p.spec.priority_class_name = cls
            p.spec.preemption_policy = ""
            return p

        named = m.dispatch("create", "pods", namespace="default",
                           body=fresh("a", cls="high"))
        assert named.spec.priority == 1000
        assert named.spec.preemption_policy == api.PreemptNever
        defaulted = m.dispatch("create", "pods", namespace="default",
                               body=fresh("b"))
        assert defaulted.spec.priority == 100  # globalDefault applied
        with pytest.raises(errors.StatusError):
            m.dispatch("create", "pods", namespace="default",
                       body=fresh("c", cls="no-such-class"))
        with pytest.raises(errors.StatusError):
            # explicit priority conflicting with the class value
            m.dispatch("create", "pods", namespace="default",
                       body=fresh("d", cls="high", prio=5))

    def test_global_default_uniqueness_and_value_immutable(self):
        m = Master()
        ctx = Context()
        m.priorityclasses.create(ctx, api.PriorityClass(
            metadata=api.ObjectMeta(name="a"), value=1,
            global_default=True))
        with pytest.raises(errors.StatusError):
            m.priorityclasses.create(ctx, api.PriorityClass(
                metadata=api.ObjectMeta(name="b"), value=2,
                global_default=True))
        got = m.priorityclasses.get(ctx, "a")
        got.value = 99
        with pytest.raises(errors.StatusError):
            m.priorityclasses.update(ctx, got)

    def test_wire_roundtrip_all_versions(self):
        from kubernetes_tpu.api.latest import scheme
        pc = api.PriorityClass(metadata=api.ObjectMeta(name="x"),
                               value=42, global_default=True,
                               description="d")
        for v in ("v1", "v1beta1", "v1beta2"):
            dec = scheme.decode(scheme.encode(pc, v))
            assert (dec.value, dec.global_default, dec.metadata.name) == \
                (42, True, "x")
        pod = mkpod("p", prio=7)
        pod.spec.priority_class_name = "x"
        for v in ("v1", "v1beta1", "v1beta2"):
            dec = scheme.decode(scheme.encode(pod, v))
            assert dec.spec.priority == 7
            assert dec.spec.priority_class_name == "x"


class TestLiveStack:
    def test_full_cluster_storm_pod_preempts_end_to_end(self):
        from kubernetes_tpu.scheduler.driver import ConfigFactory
        from kubernetes_tpu.scheduler.tpu_batch import BatchScheduler

        m = Master()
        client = Client(InProcessTransport(m))
        for i in range(2):
            client.nodes().create(api.Node(
                metadata=api.ObjectMeta(name=f"n{i}"),
                spec=api.NodeSpec(capacity={"cpu": Quantity("1"),
                                            "memory": Quantity("4Gi")})))
        client.resource("priorityclasses").create(api.PriorityClass(
            metadata=api.ObjectMeta(name="high"), value=1000))

        def pod(name, cls=""):
            return api.Pod(
                metadata=api.ObjectMeta(name=name, namespace="default"),
                spec=api.PodSpec(containers=[api.Container(
                    name="c", image="i",
                    resources=api.ResourceRequirements(limits={
                        "cpu": Quantity("500m"),
                        "memory": Quantity("128Mi")}))],
                    priority_class_name=cls))

        factory = ConfigFactory(client)
        config = factory.create()
        sched = BatchScheduler(config, factory, client,
                               wave_linger_s=0.01).run()
        try:
            for i in range(4):
                client.pods().create(pod(f"low-{i}"))
            deadline = time.time() + 30
            while time.time() < deadline:
                if sum(1 for p in client.pods().list().items
                       if p.spec.host) == 4:
                    break
                time.sleep(0.05)
            else:
                pytest.fail("low pods never filled the cluster")
            client.pods().create(pod("storm", cls="high"))
            deadline = time.time() + 30
            storm = None
            while time.time() < deadline:
                try:
                    storm = client.pods().get("storm")
                    if storm.spec.host:
                        break
                except errors.StatusError:
                    pass
                time.sleep(0.05)
            assert storm is not None and storm.spec.host, \
                "storm pod never bound into the full cluster"
            # victims evicted: 4 low + 1 storm - 2 victims = 3 remain
            remaining = client.pods().list().items
            assert len(remaining) == 3
            assert {p.metadata.name for p in remaining} >= {"storm"}
        finally:
            sched.stop()
            factory.stop()
