"""IncrementalEncoder vs full encoder — decision equivalence under churn.

The incremental encoder's arrays differ from the full encoder's (sticky
vocabulary order, pow-2 padding, resident group rows), but the DECISIONS the
solver derives from them must be identical for every wave, and both must
match the serial oracle. Fuzzed over multi-wave churn traces with pod
creates/deletes, binds, node-label dependence, services, gangs, and extended
resources.
"""

import random

import numpy as np
import pytest

from kubernetes_tpu.api import types as api
from kubernetes_tpu.api.quantity import Quantity
from kubernetes_tpu.models import gang
from kubernetes_tpu.models.batch_solver import (
    decisions_to_names,
    snapshot_to_inputs,
    solve,
)
from kubernetes_tpu.models.incremental import IncrementalEncoder
from kubernetes_tpu.models.oracle import solve_serial
from kubernetes_tpu.models.policy import BatchPolicy
from kubernetes_tpu.models.snapshot import encode_snapshot


def mk_node(name, cpu_m=2000, mem=4 << 30, labels=None, extra=None):
    cap = {"cpu": Quantity(f"{cpu_m}m"), "memory": Quantity(mem)}
    for k, v in (extra or {}).items():
        cap[k] = Quantity(v)
    return api.Node(metadata=api.ObjectMeta(name=name, labels=labels or {}),
                    spec=api.NodeSpec(capacity=cap))


_uid = [0]


def mk_pod(name, ns="default", cpu_m=0, mem=0, host="", labels=None,
           node_selector=None, host_ports=(), pds=(), extra=None, group=None):
    limits = {}
    if cpu_m:
        limits["cpu"] = Quantity(f"{cpu_m}m")
    if mem:
        limits["memory"] = Quantity(mem)
    for k, v in (extra or {}).items():
        limits[k] = Quantity(v)
    ann = {}
    if group:
        ann[gang.GANG_NAME_ANNOTATION] = group
    _uid[0] += 1
    return api.Pod(
        metadata=api.ObjectMeta(name=name, namespace=ns,
                                uid=f"uid-{_uid[0]}", labels=labels or {},
                                annotations=ann),
        spec=api.PodSpec(
            host=host, node_selector=node_selector or {},
            containers=[api.Container(
                name="c", image="i",
                ports=[api.ContainerPort(container_port=80 + i, host_port=p)
                       for i, p in enumerate(host_ports)],
                resources=api.ResourceRequirements(limits=limits))],
            volumes=[api.Volume(name=f"v{i}", source=api.VolumeSource(
                gce_persistent_disk=api.GCEPersistentDiskVolumeSource(
                    pd_name=pd))) for i, pd in enumerate(pds)]),
        status=api.PodStatus(host=host))


def assert_wave_equivalent(enc, nodes, existing, pending, services=()):
    """Incremental decisions == full-encode decisions == serial oracle."""
    inc = enc.encode(nodes, existing, pending, services)
    chosen_inc, _ = solve(inc)
    got = decisions_to_names(inc, chosen_inc)
    full = encode_snapshot(nodes, existing, pending, services,
                           policy=enc.policy)
    chosen_full, _ = solve(full)
    want = decisions_to_names(full, chosen_full)
    assert got == want, f"incremental={got}\nfull       ={want}"
    serial = solve_serial(nodes, existing, pending, services, gangs=True)
    assert want == serial, f"batch={want}\nserial={serial}"
    return got


def test_single_wave_matches_full():
    enc = IncrementalEncoder()
    nodes = [mk_node(f"n{i}") for i in range(4)]
    pending = [mk_pod(f"p{i}", cpu_m=100, mem=64 << 20) for i in range(6)]
    assert_wave_equivalent(enc, nodes, [], pending)


def test_pod_axis_padding_is_inert():
    """Wave sizes 1..9 share pow-2 buckets; padding rows never place."""
    enc = IncrementalEncoder()
    nodes = [mk_node(f"n{i}") for i in range(3)]
    existing = []
    for wave in range(1, 10):
        pending = [mk_pod(f"w{wave}p{i}", cpu_m=50) for i in range(wave)]
        got = assert_wave_equivalent(enc, nodes, existing, pending)
        assert len(got) == wave
        for p, h in zip(pending, got):
            if h:
                p.status.host = h
                existing.append(p)


def test_incremental_tracks_binds_and_deletes():
    enc = IncrementalEncoder()
    nodes = [mk_node("a", cpu_m=1000, mem=1 << 30),
             mk_node("b", cpu_m=1000, mem=1 << 30)]
    existing = []
    # wave 1: fill node capacity
    p1 = [mk_pod(f"p{i}", cpu_m=400, mem=128 << 20) for i in range(4)]
    got = assert_wave_equivalent(enc, nodes, existing, p1)
    for p, h in zip(p1, got):
        p.status.host = h
        existing.append(p)
    # wave 2: cluster full (2x1000m - 4x400m = 200m free per node)
    p2 = [mk_pod("q0", cpu_m=400, mem=128 << 20),
          mk_pod("q1", cpu_m=400, mem=128 << 20)]
    got = assert_wave_equivalent(enc, nodes, existing, p2)
    assert got == [None, None]
    # delete two pods (one per node under LR spreading), capacity frees up
    del existing[0:2]
    p3 = [mk_pod("r0", cpu_m=400, mem=128 << 20),
          mk_pod("r1", cpu_m=400, mem=128 << 20)]
    got = assert_wave_equivalent(enc, nodes, existing, p3)
    assert None not in got


def test_node_change_triggers_consistent_rebuild():
    enc = IncrementalEncoder()
    nodes = [mk_node("a"), mk_node("b")]
    pending = [mk_pod("p0", cpu_m=100)]
    assert_wave_equivalent(enc, nodes, [], pending)
    nodes = nodes + [mk_node("c", labels={"zone": "z2"})]
    pending = [mk_pod("p1", cpu_m=100, node_selector={"zone": "z2"})]
    got = assert_wave_equivalent(enc, nodes, [], pending)
    assert got == ["c"]


def test_label_policy_planes_supported():
    pol = BatchPolicy(label_presence=((("blessed",), True),),
                      label_prefs=(("fast", True, 2),),
                      anti_affinity=(("zone", 1),))
    enc = IncrementalEncoder(pol)
    nodes = [mk_node("a", labels={"blessed": "1", "zone": "z1"}),
             mk_node("b", labels={"blessed": "1", "fast": "1", "zone": "z2"}),
             mk_node("c", labels={"zone": "z1"})]  # not blessed -> filtered
    svc = api.Service(metadata=api.ObjectMeta(name="s", namespace="default"),
                      spec=api.ServiceSpec(port=80, selector={"app": "x"}))
    pending = [mk_pod(f"p{i}", labels={"app": "x"}) for i in range(4)]

    inc = enc.encode(nodes, [], pending, [svc])
    chosen_inc, _ = solve(inc)
    got = decisions_to_names(inc, chosen_inc)
    full = encode_snapshot(nodes, [], pending, [svc], policy=pol)
    chosen_full, _ = solve(full)
    assert got == decisions_to_names(full, chosen_full)
    assert "c" not in got


def test_existing_pod_counts_in_every_matching_group():
    """An existing pod whose labels satisfy several services' selectors is
    a spreading peer of ALL of them (full encoder's member_exist matrix),
    not just of its own first service — regression for the incremental
    single-group counting bug."""
    enc = IncrementalEncoder()
    nodes = [mk_node("n0", cpu_m=4000, mem=8 << 30),
             mk_node("n1", cpu_m=4000, mem=8 << 30)]
    services = [
        api.Service(metadata=api.ObjectMeta(name="s0", namespace="default"),
                    spec=api.ServiceSpec(port=80, selector={"a": "1"})),
        api.Service(metadata=api.ObjectMeta(name="s1", namespace="default"),
                    spec=api.ServiceSpec(port=80, selector={"b": "2"})),
    ]
    # bound pod matches BOTH selectors; loader pod biases n1's resources
    both = mk_pod("both", labels={"a": "1", "b": "2"}, host="n0")
    loader = mk_pod("load", cpu_m=2000, mem=2 << 30, host="n1")
    existing = [both, loader]
    # warm the encoder's resident planes before the decisive wave
    assert_wave_equivalent(enc, nodes, existing, [mk_pod("warm")], services)
    # pending pod matches only s1 — 'both' must count as its n0 peer
    pending = [mk_pod("p", labels={"b": "2"})]
    assert_wave_equivalent(enc, nodes, existing, pending, services)


# -- ServiceAffinity anchors (CheckServiceAffinity) ---------------------------

AFFINITY = BatchPolicy(affinity_labels=("region",),
                       anti_affinity=(("zone", 2),))


def _region_nodes(rng, n):
    """Two regions of zones, some nodes without a region or a zone."""
    nodes = []
    for i in range(n):
        r = rng.choice(["r1", "r2"])
        labels = {"region": r, "zone": f"{r}-{rng.choice('ab')}"}
        if rng.random() < 0.15:
            labels.pop("region")
        if rng.random() < 0.1:
            labels.pop("zone")
        nodes.append(mk_node(f"n{i:02d}", cpu_m=rng.choice([1000, 2000]),
                             mem=4 << 30, labels=labels))
    return nodes


_SERVICES = [api.Service(metadata=api.ObjectMeta(name=f"s{k}",
                                                 namespace=ns),
                         spec=api.ServiceSpec(port=80, selector=sel))
             for k, (ns, sel) in enumerate([
                 ("default", {"app": "web"}), ("default", {"app": "db"}),
                 ("default", {"tier": "front"}), ("tenant", {"app": "web"})])]


def _affinity_pod(rng, name):
    kw = dict(cpu_m=rng.choice([0, 100, 300]), mem=64 << 20,
              ns=rng.choice(["default", "default", "tenant"]))
    if rng.random() < 0.85:
        kw["labels"] = {"app": rng.choice(["web", "db"])}
        if rng.random() < 0.3:
            kw["labels"]["tier"] = "front"
    if rng.random() < 0.15:
        kw["node_selector"] = {"region": rng.choice(["r1", "r2", "r9"])}
    return mk_pod(name, **kw)


def _decide_both(enc, nodes, existing, pending, snap=None):
    """Decisions of the incremental snapshot (``snap``, else encode())
    and of the full encoder over the same list; they have to agree."""
    inc = snap if snap is not None else enc.encode(nodes, existing, pending,
                                                   _SERVICES)
    got = decisions_to_names(inc, solve(inc)[0])
    full = encode_snapshot(nodes, existing, pending, _SERVICES,
                           policy=enc.policy)
    want = decisions_to_names(full, solve(full)[0])
    assert got == want, f"incremental={got}\nfull       ={want}"
    return inc, got


@pytest.mark.parametrize("seed", range(6))
def test_fuzz_affinity_churn_equivalence(seed):
    """CheckServiceAffinity + zone anti-affinity under churn, through both
    of the encoder's doors (encode and encode_delta): binds, removal of a
    group's anchor peer (the next takes over), an anchor on a node off the
    list, an identical relist. The existing list stays in commit order, so
    the full encoder's first-in-list peer is the first committed one."""
    import copy
    rng = random.Random(4200 + seed)
    nodes = _region_nodes(rng, rng.randint(6, 14))
    ghost = mk_pod("ghost", labels={"app": "db"}, host="gone")
    existing = [ghost] if seed % 2 else []
    enc = IncrementalEncoder(AFFINITY)
    upserted, removed = [], []
    anchored = unknown = 0
    for wave in range(6):
        pending = [_affinity_pod(rng, f"w{wave}p{i}")
                   for i in range(rng.randint(1, 10))]
        snap = None
        if wave and rng.random() < 0.5:
            snap = enc.encode_delta(nodes, upserted, removed, pending,
                                    _SERVICES)
        inc, got = _decide_both(enc, nodes, existing, pending, snap)
        gid = inc.pod_gid[:len(pending)]
        open_ = (inc.pod_aff_static[:len(pending)] == -2).all(axis=1)
        anchored += int((open_ & (gid >= 0)
                         & inc.has_anchor0[np.maximum(gid, 0)]).sum())
        unknown += int((inc.pod_aff_static[:, 0] == -100).sum())
        upserted, removed = [], []
        for p, h in zip(pending, got):
            if h:
                p.status.host = h
                existing.append(p)
                upserted.append(p)
        # the anchor of some group leaves: its first committed peer (the
        # ghost, off the list, stays until the fourth wave)
        peers = [p for p in existing if p is not ghost or wave == 3]
        if peers and (rng.random() < 0.6 or wave == 3):
            existing.remove(peers[0])
            removed.append(peers[0])
        if rng.random() < 0.3:
            # a relist: new objects, the same pods in the same order
            existing = [copy.deepcopy(p) for p in existing]
            upserted = []
            removed = []
            inc, _ = _decide_both(enc, nodes, existing, [])
    assert anchored > 0
    if seed % 2:
        assert unknown > 0      # the ghost anchored some db pods nowhere


def test_the_anchor_is_the_first_committed_peer_through_a_reordering_relist():
    """A relist that reorders the list: the full encoder takes the first
    peer of the list; this encoder keeps the first committed, as
    serial_policy's rule says — across a node-plane rebuild too."""
    nodes = [mk_node("a", labels={"region": "r1", "zone": "z1"}),
             mk_node("b", labels={"region": "r2", "zone": "z2"})]
    svc = _SERVICES[:1]
    first = mk_pod("first", labels={"app": "web"}, host="a")
    second = mk_pod("second", labels={"app": "web"}, host="b")
    enc = IncrementalEncoder(AFFINITY)
    pend = [mk_pod("p", labels={"app": "web"})]
    snap = enc.encode(nodes, [first, second], pend, svc)
    assert snap.has_anchor0[0] and snap.anchor_vals0[0, 0] == \
        snap.node_aff_vals[0, 0]
    relisted = [second, first]
    snap = enc.encode(nodes, relisted, pend, svc)
    assert decisions_to_names(snap, solve(snap)[0]) == ["a"]
    full = encode_snapshot(nodes, relisted, pend, svc, policy=AFFINITY)
    assert decisions_to_names(full, solve(full)[0]) == ["b"]
    nodes = nodes + [mk_node("c", labels={"region": "r2", "zone": "z3"})]
    snap = enc.encode(nodes, relisted, pend, svc)
    assert decisions_to_names(snap, solve(snap)[0]) == ["a"]
    # the first committed peer leaves: the next one is the anchor (r2),
    # and inside r2 the zone without a peer
    snap = enc.encode(nodes, [second], pend, svc)
    assert decisions_to_names(snap, solve(snap)[0]) == ["c"]


def test_anchor_maintenance_is_o_changed():
    """One bind and one delete write the commit-order peer lists once a
    group each — whatever the number of bound peers — and rebuild no node
    plane."""
    rng = random.Random(9)
    nodes = _region_nodes(rng, 24)
    existing = [mk_pod(f"e{i}", labels={"app": "web"},
                       host=f"n{i % 24:02d}") for i in range(200)]
    enc = IncrementalEncoder(AFFINITY)
    enc.encode(nodes, existing, [mk_pod("warm", labels={"app": "web"})],
               _SERVICES)
    rebuilds = enc.op_counts["node_rebuilds"]
    aw0 = enc.op_counts["anchor_writes"]
    newpod = mk_pod("np", labels={"app": "web"}, host="n03")
    snap = enc.encode_delta(nodes, [newpod], [existing[0]],
                            [mk_pod("pend", labels={"app": "web"})],
                            _SERVICES)
    assert snap is not None and snap.has_anchor0[0]
    assert enc.op_counts["node_rebuilds"] == rebuilds
    # one group ({"app": "web"} in default), two changed pods
    assert enc.op_counts["anchor_writes"] - aw0 == 2
    # the anchor moved on to the next committed peer
    want = snap.node_aff_vals[enc._node_index[existing[1].status.host]]
    assert np.array_equal(snap.anchor_vals0[0], want)
    # an encoder without ServiceAffinity keeps no such list
    plain = IncrementalEncoder()
    plain.encode(nodes, existing, [], _SERVICES)
    assert plain.op_counts["anchor_writes"] == 0


def test_checkpoint_restore_carries_the_anchors():
    rng = random.Random(17)
    nodes = _region_nodes(rng, 12)
    existing = [mk_pod(f"e{i}", labels={"app": rng.choice(["web", "db"])},
                       host=f"n{rng.randrange(12):02d}") for i in range(30)]
    enc = IncrementalEncoder(AFFINITY)
    pending = [_affinity_pod(rng, f"p{i}") for i in range(8)]
    snap0 = enc.encode(nodes, existing, pending, _SERVICES)
    ckpt = enc.checkpoint()
    # every peer of both groups leaves, then the checkpoint comes back
    gone = [p for p in existing if p.metadata.namespace == "default"]
    snap = enc.encode_delta(nodes, [], gone, pending, _SERVICES)
    assert snap is not None and not snap.has_anchor0.any()
    enc.restore(ckpt)
    snap = enc.encode_delta(nodes, [], [], pending, _SERVICES)
    assert np.array_equal(snap.has_anchor0, snap0.has_anchor0)
    assert np.array_equal(snap.anchor_vals0, snap0.anchor_vals0)
    assert np.array_equal(snap.pod_aff_static, snap0.pod_aff_static)
    _decide_both(enc, nodes, existing, pending, snap)
    # the checkpoint stays restorable: the same anchors a second time
    enc.restore(ckpt)
    assert enc.resident_fingerprint() == \
        (enc.restore(ckpt) or enc.resident_fingerprint())


def test_compiled_shape_count_bounded_under_churn():
    """Steady-state churn must re-use compiled programs: track the set of
    distinct solver input shape signatures across 30 waves of varying size
    and content; the pow-2 buckets keep it small."""
    enc = IncrementalEncoder()
    rng = random.Random(5)
    nodes = [mk_node(f"n{i}") for i in range(16)]
    existing = []
    shapes = set()
    for wave in range(30):
        size = rng.randint(3, 9)  # spans the 4-, 8- and 16-pod buckets
        pending = [mk_pod(f"w{wave}p{i}", cpu_m=rng.choice([50, 100]),
                          mem=64 << 20,
                          host_ports=(rng.choice([8080, 9090]),)
                          if rng.random() < 0.3 else ())
                   for i in range(size)]
        snap = enc.encode(nodes, existing, pending)
        inp = snapshot_to_inputs(snap)
        shapes.add(tuple((a.shape, str(a.dtype)) for a in inp))
        chosen, _ = solve(snap)
        for p, h in zip(pending, decisions_to_names(snap, chosen)):
            if h:
                p.status.host = h
                existing.append(p)
        while len(existing) > 40:    # deletes churn the planes too
            existing.pop(rng.randrange(len(existing)))
    # one shape per touched pow-2 pod bucket (4/8/16); nothing per-wave
    assert len(shapes) <= 3, f"{len(shapes)} distinct compiled shapes"


@pytest.mark.parametrize("seed", range(6))
def test_fuzz_churn_equivalence(seed):
    rng = random.Random(3000 + seed)
    zones = ["z1", "z2"]
    nodes = [mk_node(f"n{i}", cpu_m=rng.choice([1000, 2000]),
                     mem=rng.choice([2 << 30, 4 << 30]),
                     labels={"zone": rng.choice(zones)} if rng.random() < 0.6
                     else {},
                     extra={"nvidia.com/gpu": 2} if rng.random() < 0.3
                     else None)
             for i in range(rng.randint(3, 10))]
    # overlapping selectors: one pod can satisfy several services
    sels = [{"app": "a0"}, {"app": "a1"}, {"tier": "web"},
            {"app": "a0", "tier": "web"}]
    services = [api.Service(
        metadata=api.ObjectMeta(name=f"svc{k}", namespace="default"),
        spec=api.ServiceSpec(port=80, selector=sels[k]))
        for k in range(rng.randint(0, 4))]
    enc = IncrementalEncoder()
    existing = []
    for wave in range(rng.randint(2, 5)):
        pending = []
        for i in range(rng.randint(1, 12)):
            kw = dict(cpu_m=rng.choice([0, 100, 400]),
                      mem=rng.choice([0, 64 << 20, 256 << 20]))
            if rng.random() < 0.4:
                kw["labels"] = {"app": f"a{rng.randint(0, 2)}"}
                if rng.random() < 0.5:
                    kw["labels"]["tier"] = "web"
            if rng.random() < 0.25:
                kw["host_ports"] = (rng.choice([8080, 9090, 7070]),)
            if rng.random() < 0.2:
                kw["node_selector"] = {"zone": rng.choice(zones)}
            if rng.random() < 0.15:
                kw["pds"] = (rng.choice(["pd1", "pd2"]),)
            if rng.random() < 0.2:
                kw["extra"] = {"nvidia.com/gpu": 1}
            if rng.random() < 0.25:
                kw["group"] = f"g{wave}x{rng.randint(0, 1)}"
            pending.append(mk_pod(f"w{wave}p{i}", **kw))
        pending = gang.order_wave(pending)
        got = assert_wave_equivalent(enc, nodes, existing, pending, services)
        for p, h in zip(pending, got):
            if h:
                p.status.host = h
                existing.append(p)
        for _ in range(rng.randint(0, 4)):
            if existing:
                existing.pop(rng.randrange(len(existing)))


# -- O(changed) delta path ---------------------------------------------------

@pytest.mark.parametrize("seed", range(4))
def test_fuzz_delta_equivalence(seed):
    """encode_delta fed from churn deltas must match a fresh full encode
    (and therefore the serial oracle) wave after wave — adds, host
    changes, removals, service groups, pinned hosts, gangs."""
    rng = random.Random(7000 + seed)
    nodes = [mk_node(f"n{i}", cpu_m=rng.choice([1000, 2000]),
                     labels={"zone": rng.choice(["z1", "z2"])})
             for i in range(rng.randint(3, 8))]
    services = [api.Service(
        metadata=api.ObjectMeta(name="web", namespace="default"),
        spec=api.ServiceSpec(port=80, selector={"app": "web"}))]
    enc = IncrementalEncoder()
    existing = []
    # first wave establishes planes through the full path
    snap = enc.encode(nodes, existing, [], services)
    for wave in range(4):
        pending = [mk_pod(f"w{wave}p{i}",
                          cpu_m=rng.choice([0, 100, 400]),
                          labels={"app": "web"} if rng.random() < 0.5
                          else {})
                   for i in range(rng.randint(1, 10))]
        upserted, removed = [], []
        # simulate binds from previous waves arriving as deltas
        for p in list(existing):
            if rng.random() < 0.15:
                existing.remove(p)
                removed.append(p)
        snap = enc.encode_delta(nodes, upserted, removed, pending, services)
        assert snap is not None
        fresh = IncrementalEncoder().encode(nodes, existing, pending,
                                            services)
        chosen_d, _ = solve(snap)
        chosen_f, _ = solve(fresh)
        assert decisions_to_names(snap, chosen_d) == \
            decisions_to_names(fresh, chosen_f)
        # commit this wave's decisions as delta upserts for the next
        names = decisions_to_names(snap, chosen_d)
        ups = []
        for p, h in zip(pending, names):
            if h:
                p.status.host = h
                existing.append(p)
                ups.append(p)
        snap2 = enc.encode_delta(nodes, ups, [], [], services)
        assert snap2 is not None


def test_delta_bails_to_full_on_overflow_and_node_change():
    enc = IncrementalEncoder()
    nodes = [mk_node("n1", cpu_m=500)]
    enc.encode(nodes, [], [], [])
    # capacity overflow: two 400m pods on a 500m node
    over = []
    for i in range(2):
        p = mk_pod(f"e{i}", cpu_m=400)
        p.status.host = "n1"
        over.append(p)
    assert enc.encode_delta(nodes, over, [], [], []) is None
    # full path still encodes (order-exact greedy walk)
    snap = enc.encode(nodes, over, [], [])
    assert snap is not None
    # node-set change: delta refuses
    enc2 = IncrementalEncoder()
    enc2.encode(nodes, [], [], [])
    assert enc2.encode_delta([mk_node("n2")], [], [], [], []) is None


# -- resident zone-count planes (ServiceAntiAffinity) ------------------------

def _zone_fixture(n_nodes=32, n_existing=64):
    pol = BatchPolicy(anti_affinity=(("zone", 2),))
    enc = IncrementalEncoder(pol)
    nodes = [mk_node(f"n{i}", labels={"zone": f"z{i % 4}"} if i % 5 else {})
             for i in range(n_nodes)]
    svc = api.Service(metadata=api.ObjectMeta(name="s", namespace="default"),
                      spec=api.ServiceSpec(port=80, selector={"app": "x"}))
    existing = [mk_pod(f"e{i}", labels={"app": "x"} if i % 2 else {},
                       host=f"n{i % n_nodes}") for i in range(n_existing)]
    return pol, enc, nodes, [svc], existing


def test_zone_count_planes_stay_exact_under_delta_churn():
    """The resident [A, G, V] zone-count planes must equal the from-scratch
    derivation (batch_solver.derive_zone_counts) after every delta, and
    the delta-path decisions must match a fresh encoder's and the full
    encoder's under an anti-affinity policy."""
    from kubernetes_tpu.models.batch_solver import derive_zone_counts

    pol, enc, nodes, services, existing = _zone_fixture()
    enc.encode(nodes, existing, [mk_pod("warm", labels={"app": "x"})],
               services)
    rng = random.Random(11)
    for wave in range(4):
        pending = [mk_pod(f"w{wave}p{j}",
                          labels={"app": "x"} if rng.random() < 0.7 else {})
                   for j in range(rng.randint(2, 6))]
        upserted, removed = [], []
        for p in list(existing):
            if rng.random() < 0.1:
                existing.remove(p)
                removed.append(p)
        snap = enc.encode_delta(nodes, upserted, removed, pending, services)
        assert snap is not None
        want = derive_zone_counts(snap.node_zone, snap.group_counts,
                                  snap.zone_counts0.shape[2])
        assert np.array_equal(snap.zone_counts0, want)
        fresh = IncrementalEncoder(pol).encode(nodes, existing, pending,
                                               services)
        full = encode_snapshot(nodes, existing, pending, services,
                               policy=pol)
        chosen_d, _ = solve(snap)
        chosen_fr, _ = solve(fresh)
        chosen_fu, _ = solve(full)
        assert decisions_to_names(snap, chosen_d) == \
            decisions_to_names(fresh, chosen_fr) == \
            decisions_to_names(full, chosen_fu)
        for p, h in zip(pending, decisions_to_names(snap, chosen_d)):
            if h:
                p.status.host = h
                existing.append(p)
                enc.encode_delta(nodes, [p], [], [], services)


def test_zone_plane_maintenance_is_o_changed():
    """Counter-based O(changed) guard (tier-1 safe: no timing): one pod
    bind + one delete must touch the zone planes a constant number of
    times — A dims x matching groups per pod — independent of cluster
    size, and must not trigger a node-plane rebuild."""
    pol, enc, nodes, services, existing = _zone_fixture()
    enc.encode(nodes, existing, [mk_pod("warm", labels={"app": "x"})],
               services)
    rebuilds = enc.op_counts["node_rebuilds"]
    zw0 = enc.op_counts["zone_writes"]
    newpod = mk_pod("np", labels={"app": "x"})
    newpod.status.host = "n3"
    gone = existing[1]  # labeled {"app": "x"}, on a zone-labeled node
    snap = enc.encode_delta(nodes, [newpod], [gone],
                            [mk_pod("pend", labels={"app": "x"})], services)
    assert snap is not None
    assert enc.op_counts["node_rebuilds"] == rebuilds
    # A=1 anti-affinity dim, 1 matching group, 2 changed pods -> <= 2
    # single-element writes; the resident planes were NOT rebuilt from
    # the 64-pod existing list
    assert enc.op_counts["zone_writes"] - zw0 <= 2


def test_store_changelog_and_modeler_delta():
    from kubernetes_tpu.client.cache import FIFO, Store
    from kubernetes_tpu.scheduler.driver import SimpleModeler

    s = Store()
    t0 = s.token()
    a, b = mk_pod("a"), mk_pod("b")
    s.add(a); s.add(b); s.delete(a)
    events, t1 = s.delta_since(t0)
    assert [op for op, _ in events] == ["set", "set", "delete"]
    assert s.delta_since(t1) == ([], t1)
    # kube-slipstream: a relist DIFFS against the cache instead of
    # invalidating every token — identical contents log nothing, a
    # vanished object logs a delete, and consumers replay through
    s.replace([b])
    assert s.delta_since(t1) == ([], t1)
    s.replace([])
    events, t2 = s.delta_since(t1)
    assert [(op, o.metadata.name) for op, o in events] == [("delete", "b")]
    # only a diff wider than the retained window breaks tokens
    s.add(b)
    t3 = s.token()
    try:
        Store._LOG_MAX = 1
        s.replace([mk_pod("c"), mk_pod("d")])
    finally:
        Store._LOG_MAX = 1 << 14
    assert s.delta_since(t3) is None

    m = SimpleModeler(FIFO(), Store())
    tok = m.token()
    p = mk_pod("p1"); p.status.host = "n1"
    m.assume_pod(p)
    ups, rms, tok = m.delta(tok)
    assert [x.metadata.name for x in ups] == ["p1"] and rms == []
    # the reflector catches the bind: assumed -> scheduled is a
    # migration, never a removal
    m.scheduled.add(p)
    ups, rms, tok = m.delta(tok)   # prune fires inside delta
    assert rms == [] and [x.metadata.name for x in ups] == ["p1"]
    # true deletion: gone from both stores
    m.scheduled.delete(p)
    ups, rms, tok = m.delta(tok)
    assert ups == [] and [x.metadata.name for x in rms] == ["p1"]
    # delete + recreate of the same NAME with a new uid inside one
    # window: the old uid must surface as removed (else its resources
    # leak in the encoder) and the new one as upserted
    old = mk_pod("p2"); old.metadata.uid = "uid-old"
    m.scheduled.add(old)
    ups, rms, tok = m.delta(tok)
    m.scheduled.delete(old)
    new = mk_pod("p2"); new.metadata.uid = "uid-new"
    m.scheduled.add(new)
    ups, rms, tok = m.delta(tok)
    assert [x.metadata.uid for x in ups] == ["uid-new"]
    assert [x.metadata.uid for x in rms] == ["uid-old"]
