"""kube-slipstream: journal-replay encoder resync + AOT shape-bucket prewarm.

Two contracts under test (scheduler/tpu_batch.py, solver/prewarm.py):

- **resync**: an IncrementalEncoder checkpoint is an exact, reusable
  restore point, and restoring it + replaying the modeler changelog
  (``encode_delta`` over the missed upserts/removes) reconstructs the
  bit-identical resident state the full diff-walk would have built —
  same solver decisions as a from-scratch ``encode_snapshot``, and a
  subsequent full ``encode()`` is a fingerprint NO-OP. Falling back to
  the O(cluster) re-encode happens only when the journal cannot cover
  the gap, counted by reason (``encoder_resync_full_total``).
- **prewarm**: the fill-triggered/boot-set background compile never
  blocks or corrupts a live wave — a solve racing a prewarm compile
  returns the same decisions as an unraced solve (the program cache is
  only ever extended with complete executables).
"""

import random
import threading
import time
from types import SimpleNamespace

import pytest

from kubernetes_tpu import watch as watchpkg
from kubernetes_tpu.addons.monitoring import (
    SLOWatchdog,
    default_churn_rules,
)
from kubernetes_tpu.api import types as api
from kubernetes_tpu.api.quantity import Quantity
from kubernetes_tpu.apiserver.master import Master
from kubernetes_tpu.client.cache import FIFO, ListWatch, Reflector, Store
from kubernetes_tpu.client.client import Client, InProcessTransport
from kubernetes_tpu.models.batch_solver import (
    decisions_to_names,
    peer_bound_of,
    snapshot_to_host_inputs,
    solve,
    warm_compile,
)
from kubernetes_tpu.models.incremental import IncrementalEncoder
from kubernetes_tpu.models.policy import BatchPolicy
from kubernetes_tpu.models.snapshot import encode_snapshot
from kubernetes_tpu.scheduler import tpu_batch
from kubernetes_tpu.scheduler.driver import ConfigFactory, SimpleModeler
from kubernetes_tpu.scheduler.tpu_batch import BatchScheduler
from kubernetes_tpu.solver.prewarm import PrewarmController, pow2_ladder
from kubernetes_tpu.solver.service import _dims_of, _pad_inputs
from kubernetes_tpu.util import metrics


def mk_node(name, cpu_m=16000, mem=64 << 30, labels=None):
    return api.Node(metadata=api.ObjectMeta(name=name, labels=labels or {}),
                    spec=api.NodeSpec(capacity={
                        "cpu": Quantity(f"{cpu_m}m"),
                        "memory": Quantity(mem)}))


_uid = [0]


def mk_pod(name, ns="default", cpu_m=100, mem=64 << 20, host="",
           host_ports=()):
    _uid[0] += 1
    return api.Pod(
        metadata=api.ObjectMeta(name=name, namespace=ns,
                                uid=f"slip-{_uid[0]}"),
        spec=api.PodSpec(
            host=host,
            containers=[api.Container(
                name="c", image="i",
                ports=[api.ContainerPort(container_port=80 + i, host_port=p)
                       for i, p in enumerate(host_ports)],
                resources=api.ResourceRequirements(limits={
                    "cpu": Quantity(f"{cpu_m}m"),
                    "memory": Quantity(mem)}))]),
        status=api.PodStatus(host=host))


def _decisions(snap):
    chosen, _ = solve(snap)
    return decisions_to_names(snap, chosen)


def _full_decisions(nodes, existing, pending, policy):
    return _decisions(encode_snapshot(nodes, existing, pending,
                                      policy=policy))


# -- checkpoint / restore ----------------------------------------------------


def test_checkpoint_before_first_wave_raises():
    enc = IncrementalEncoder()
    with pytest.raises(ValueError):
        enc.checkpoint()


def test_checkpoint_restore_exact():
    """restore() is a wholesale reset to the checkpointed planes: the
    fingerprint returns bit-exact, later mutation is dropped, and the
    checkpoint survives any number of restores."""
    enc = IncrementalEncoder()
    nodes = [mk_node(f"n{i}") for i in range(4)]
    existing = []
    p1 = [mk_pod(f"a{i}") for i in range(5)]
    for p, h in zip(p1, _decisions(enc.encode(nodes, existing, p1))):
        p.status.host = p.spec.host = h
        existing.append(p)
    enc.encode(nodes, existing, [mk_pod("probe0")])
    fp0 = enc.resident_fingerprint()
    ck = enc.checkpoint()

    # mutate well past the checkpoint: more binds, a delete, vocab growth
    p2 = [mk_pod(f"b{i}", host_ports=(30 + i,)) for i in range(4)]
    for p, h in zip(p2, _decisions(enc.encode(nodes, existing, p2))):
        p.status.host = p.spec.host = h
        existing.append(p)
    del existing[0]
    enc.encode(nodes, existing, [mk_pod("probe1")])
    assert enc.resident_fingerprint() != fp0

    for _ in range(2):  # the checkpoint is not consumed by restore
        enc.restore(ck)
        assert enc.resident_fingerprint() == fp0
    # the restored encoder schedules identically to a fresh full encode
    # over the checkpoint-time authoritative state
    probe = [mk_pod(f"c{i}") for i in range(3)]
    got = _decisions(enc.encode(nodes, p1, probe))
    assert got == _full_decisions(nodes, p1, probe, enc.policy)


# -- journal replay bit-identity ---------------------------------------------


def _assert_replay_exact(enc, nodes, upserted, removed, existing_now,
                         pending):
    """restore was already done by the caller; apply the journal and gate
    it two ways: decisions vs a from-scratch encode_snapshot twin, and
    the KTPU_DEBUG fingerprint invariant (a full diff-walk over the
    authoritative list is a NO-OP on a correctly replayed state)."""
    snap = enc.encode_delta(nodes, upserted, removed, pending)
    assert snap is not None, "journal replay unexpectedly bailed to full"
    assert _decisions(snap) == _full_decisions(nodes, existing_now, pending,
                                               enc.policy)
    before = enc.resident_fingerprint()
    enc.encode(nodes, existing_now, pending)
    assert enc.resident_fingerprint() == before


def test_replay_bit_identity_pinned():
    """Pinned fixture: the replayed events bind pods whose host-port sets
    push the ports vocabulary across a pow-2 word boundary (20 -> 40
    entries, 1 -> 2 packed uint32 words) and the pending wave crosses a
    pod-axis bucket (3 -> 6 pods, bucket 4 -> 8): replay must grow the
    buckets exactly as the live path would have."""
    enc = IncrementalEncoder()
    nodes = [mk_node(f"n{i}") for i in range(4)]
    existing = []
    seed_pods = [mk_pod(f"s{i}", host_ports=(1000 + i,)) for i in range(20)]
    for p, h in zip(seed_pods,
                    _decisions(enc.encode(nodes, existing, seed_pods))):
        p.status.host = p.spec.host = h
        existing.append(p)
    pending1 = [mk_pod(f"w{i}") for i in range(3)]
    enc.encode(nodes, existing, pending1)
    ck = enc.checkpoint()

    # journal: 20 new bound pods with 20 fresh ports + 2 deletions
    upserted = []
    for i in range(20):
        p = mk_pod(f"j{i}", host=f"n{i % 4}", host_ports=(2000 + i,))
        upserted.append(p)
    removed = [existing[0], existing[7]]
    existing2 = [p for p in existing if p not in removed] + upserted
    pending2 = [mk_pod(f"x{i}") for i in range(6)]

    enc.restore(ck)
    _assert_replay_exact(enc, nodes, upserted, removed, existing2, pending2)


@pytest.mark.parametrize("seed", [1, 7, 23])
def test_replay_fuzz(seed):
    """Random churn traces: checkpoint mid-trace, keep churning (binds,
    deletes, host migrations, vocab growth, varying wave sizes spanning
    pod-axis buckets), then restore + replay the accumulated journal and
    gate bit-identity against the from-scratch twin."""
    rng = random.Random(seed)
    enc = IncrementalEncoder()
    nodes = [mk_node(f"n{i}") for i in range(6)]
    existing = []

    def churn_wave(tag):
        pending = [mk_pod(f"{tag}p{i}", cpu_m=rng.choice((50, 100, 200)),
                          host_ports=tuple(rng.sample(range(3000, 3064),
                                                      rng.randrange(0, 3))))
                   for i in range(rng.randrange(1, 9))]
        hosts = _decisions(enc.encode(nodes, existing, pending))
        bound = []
        for p, h in zip(pending, hosts):
            if h and rng.random() < 0.8:
                p.status.host = p.spec.host = h
                existing.append(p)
                bound.append(p)
        dropped = []
        if existing and rng.random() < 0.5:
            dropped.append(existing.pop(rng.randrange(len(existing))))
        return bound, dropped

    for w in range(3):
        churn_wave(f"w{w}")
    # token-pair the checkpoint with the authoritative list: the real
    # path checkpoints right after an encode, when the resident planes
    # are in sync with the store position the journal resumes from
    enc.encode(nodes, existing, [])
    ck = enc.checkpoint()
    at_ckpt = {p.metadata.uid for p in existing}

    journal_up, journal_rm = [], []
    for w in range(3, 8):
        bound, dropped = churn_wave(f"w{w}")
        journal_up.extend(bound)
        journal_rm.extend(dropped)
    # compress like SimpleModeler.delta: upserts before removes, and a
    # delete of a uid that is still live is suppressed
    live = {p.metadata.uid for p in existing}
    upserted = [p for p in journal_up if p.metadata.uid in live]
    removed = [p for p in journal_rm
               if p.metadata.uid not in live and p.metadata.uid in at_ckpt]
    pending = [mk_pod(f"final{i}") for i in range(rng.randrange(1, 12))]

    enc.restore(ck)
    _assert_replay_exact(enc, nodes, upserted, removed, existing, pending)


# -- the scheduler resync state machine --------------------------------------


class _EncHost:
    """Minimal host exercising BatchScheduler's real resync methods
    deterministically (no wave loop, no threads) over a real
    SimpleModeler + Store changelog."""

    _encode_incremental = BatchScheduler._encode_incremental
    _replay_resync = BatchScheduler._replay_resync
    _maybe_checkpoint = BatchScheduler._maybe_checkpoint
    _debug_verify_replay = BatchScheduler._debug_verify_replay

    def __init__(self):
        self.modeler = SimpleModeler(FIFO(), Store())
        self.config = SimpleNamespace(modeler=self.modeler)
        self._encoder = IncrementalEncoder()
        self._sx = metrics.slipstream_metrics()
        self._delta_token = None
        self._ckpt = None
        self._ckpt_waves = 0
        self.checkpoint_every = 4

    def wave(self, nodes, pending):
        get_existing = lambda: self.modeler.list()  # noqa: E731
        return self._encode_incremental(nodes, pending, [], get_existing)


def _sx_counts():
    sx = metrics.slipstream_metrics()
    return {"replay": sx.resync_replay.total(),
            "full": sx.resync_full.total(),
            "window": sx.resync_full.value("window_exceeded")}


def _sx_delta(before):
    now = _sx_counts()
    return {k: now[k] - before[k] for k in now}


def test_scheduler_resync_replays_journal(monkeypatch):
    """A lost delta cursor with an intact journal replays — full
    re-encode only at encoder birth (no checkpoint yet), never again —
    with the KTPU_DEBUG bit-identity gate live."""
    monkeypatch.setattr(tpu_batch, "_DEBUG_REPLAY", True)
    host = _EncHost()
    nodes = [mk_node(f"n{i}") for i in range(4)]
    before = _sx_counts()

    # wave 1: birth — no checkpoint to replay onto, counted full
    p1 = [mk_pod(f"p{i}") for i in range(4)]
    snap = host.wave(nodes, p1)
    assert _sx_delta(before) == {"replay": 0, "full": 1, "window": 0}
    assert host._ckpt is not None and host._delta_token is not None
    for p, h in zip(p1, _decisions(snap)):
        p.status.host = p.spec.host = h
        host.modeler.scheduled.add(p)

    # wave 2: the O(changed) delta fast path — no resync at all
    before = _sx_counts()
    p2 = [mk_pod(f"q{i}") for i in range(3)]
    snap = host.wave(nodes, p2)
    assert _sx_delta(before) == {"replay": 0, "full": 0, "window": 0}
    for p, h in zip(p2, _decisions(snap)):
        p.status.host = p.spec.host = h
        host.modeler.scheduled.add(p)

    # cursor lost (watch reset / divergence heal): journal replay, zero full
    host._delta_token = None
    before = _sx_counts()
    p3 = [mk_pod(f"r{i}") for i in range(2)]
    snap = host.wave(nodes, p3)
    assert _sx_delta(before) == {"replay": 1, "full": 0, "window": 0}
    assert _decisions(snap) == _full_decisions(
        nodes, host.modeler.list(), p3, host._encoder.policy)
    assert host._delta_token is not None


def test_scheduler_resync_window_exceeded_falls_back():
    """When churn outran the store changelog ring since the last
    checkpoint, replay refuses and the full re-encode runs — counted
    under reason=window_exceeded — and stays decision-correct."""
    orig = Store._LOG_MAX
    Store._LOG_MAX = 8
    try:
        host = _EncHost()
        nodes = [mk_node(f"n{i}") for i in range(4)]
        p1 = [mk_pod(f"p{i}") for i in range(3)]
        snap = host.wave(nodes, p1)  # birth full + checkpoint
        for p, h in zip(p1, _decisions(snap)):
            p.status.host = p.spec.host = h
            host.modeler.scheduled.add(p)
        # blow the ring: more events than _LOG_MAX since the checkpoint
        for i in range(10):
            host.modeler.scheduled.add(mk_pod(f"blow{i}", host="n0"))
        host._delta_token = None
        before = _sx_counts()
        p2 = [mk_pod(f"q{i}") for i in range(2)]
        snap = host.wave(nodes, p2)
        assert _sx_delta(before) == {"replay": 0, "full": 1, "window": 1}
        assert _decisions(snap) == _full_decisions(
            nodes, host.modeler.list(), p2, host._encoder.policy)
    finally:
        Store._LOG_MAX = orig


# -- prewarm controller ------------------------------------------------------


class _Recorder:
    def __init__(self, fail=False, gate=None):
        self.targets = []
        self.fail = fail
        self.gate = gate
        self.event = threading.Event()

    def __call__(self, target):
        if self.gate is not None:
            assert self.gate.wait(5.0)
        self.targets.append(dict(target))
        self.event.set()
        if self.fail:
            raise RuntimeError("injected compile failure")


def _wait(pred, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.01)
    return False


def test_pow2_ladder():
    assert pow2_ladder(1000, floor=256) == [1024, 512, 256]
    assert pow2_ladder(256, floor=256) == [256]
    assert pow2_ladder(0) == []


def test_prewarm_fill_trigger_queues_next_bucket():
    rec = _Recorder()
    c = PrewarmController(rec, fill_fraction=0.75).start()
    try:
        bucket = {"N": 32, "N1": 33, "P": 16}
        c.observe({"P": 11}, bucket)          # 11 < 0.75 * 16: below
        assert c.pending() == 0
        c.observe({"P": 12}, bucket, frozen=("P",))  # frozen axis: never
        assert c.pending() == 0
        c.observe({"P": 12}, bucket)          # at threshold: next bucket
        assert _wait(lambda: c.compiled == 1)
        assert rec.targets == [{"N": 32, "N1": 33, "P": 32}]
        c.observe({"P": 13}, bucket)          # already compiled: dedup
        c.observe({"N": 31, "P": 2}, bucket)  # N trigger recomputes N1
        assert _wait(lambda: c.compiled == 2)
        assert rec.targets[1] == {"N": 64, "N1": 65, "P": 16}
    finally:
        c.stop()


def test_prewarm_boot_set_ready_gate():
    gate = threading.Event()
    rec = _Recorder(gate=gate)
    sx = metrics.slipstream_metrics()
    c = PrewarmController(rec).start()
    try:
        assert not c.ready()  # unarmed: boot readiness not yet claimable
        n = c.boot_set([{"N": 32, "N1": 33, "P": p}
                        for p in pow2_ladder(128, floor=64)])
        assert n == 2
        assert not c.ready() and sx.prewarm_ready.value() == 0.0
        gate.set()
        assert _wait(lambda: c.ready())
        assert c.compiled == 2 and sx.prewarm_ready.value() == 1.0
        # an empty boot set (nothing to imply a shape from) is ready now
        c2 = PrewarmController(_Recorder())
        c2.boot_set([])
        assert c2.ready()
    finally:
        c.stop()


def test_prewarm_compile_failure_is_contained():
    rec = _Recorder(fail=True)
    c = PrewarmController(rec).start()
    try:
        c.boot_set([{"P": 64}])
        assert _wait(lambda: c.errors == 1)
        assert c.compiled == 0
        assert c.ready()  # a failed bucket must not wedge the load window
        assert not c.submit({"P": 64})  # no retry: marked done
        # the thread survived: a later target still compiles
        rec.fail = False
        assert c.submit({"P": 128})
        assert _wait(lambda: c.compiled == 1)
    finally:
        c.stop()


def test_prewarm_swap_under_load():
    """A live solve racing a background warm_compile of a bigger bucket
    must never observe a half-built program: every raced solve returns
    the unraced reference decisions, and the prewarm thread's compile
    completes without error."""
    nodes = [mk_node(f"n{i}") for i in range(3)]
    pending = [mk_pod(f"p{i}") for i in range(4)]
    pol = BatchPolicy()
    snap = encode_snapshot(nodes, [], pending, policy=pol)
    ref = _decisions(snap)
    host = snapshot_to_host_inputs(snap)
    target = dict(_dims_of(host))
    target["P"] *= 2
    target["N1"] = target["N"] + 1
    errors = []

    def prewarm():
        try:
            warm_compile(_pad_inputs(host, target), pol, snap.has_gangs,
                         peer_bound_of(host))
        except Exception as e:  # noqa: BLE001 — surfaced via `errors`
            errors.append(e)

    t = threading.Thread(target=prewarm)
    t.start()
    try:
        deadline = time.monotonic() + 10.0
        while t.is_alive() and time.monotonic() < deadline:
            assert _decisions(snap) == ref
    finally:
        t.join(timeout=60.0)
    assert not t.is_alive() and not errors
    assert _decisions(snap) == ref  # and after the swap landed


# -- reflector watch resume (the journal-continuity seam) --------------------


class _ScriptedWatch:
    """Yields the scripted events, then reports a benign stream close."""

    def __init__(self, events):
        self._events = list(events)

    def next_event(self, timeout=None):
        if self._events:
            return self._events.pop(0)
        return None

    def stop(self):
        pass


class _BlockingWatch:
    def next_event(self, timeout=None):
        time.sleep(min(timeout or 0.01, 0.01))
        raise TimeoutError

    def stop(self):
        pass


def _scripted_lw(watchers):
    calls = {"list": 0, "watch": []}

    def list_fn():
        calls["list"] += 1
        return api.PodList(
            metadata=api.ListMeta(resource_version="1"),
            items=[mk_pod("seed")])

    def watch_fn(rv):
        calls["watch"].append(rv)
        return watchers.pop(0) if watchers else _BlockingWatch()

    return ListWatch(list_fn, watch_fn), calls


def _rv_pod(name, rv):
    p = mk_pod(name)
    p.metadata.resource_version = rv
    return p


def test_reflector_resumes_watch_after_progress():
    """A stream close after at least one rv-advancing event re-opens the
    watch at the last seen rv — no relist, so the store changelog the
    encoder journal replays from stays continuous."""
    lw, calls = _scripted_lw(
        [_ScriptedWatch([watchpkg.Event(watchpkg.ADDED,
                                        _rv_pod("live", "2"))])])
    store = Store()
    r = Reflector(lw, store, name="slip").run()
    try:
        assert _wait(lambda: len(calls["watch"]) >= 2)
        assert calls["list"] == 1          # never relisted
        assert r.watch_resumes == 1
        assert calls["watch"][1] == "2"    # resumed at the advanced rv
        assert store.get_by_key("default/live") is not None
    finally:
        r.stop()
        assert r.join(2.0)


def test_reflector_cold_close_still_relists():
    """A close before any progress keeps the crash-only contract: full
    relist (which Store.replace now diffs into the changelog rather than
    breaking the window)."""
    lw, calls = _scripted_lw([_ScriptedWatch([])])
    r = Reflector(lw, Store(), name="slip-cold").run()
    try:
        assert _wait(lambda: calls["list"] >= 2)
        assert r.watch_resumes == 0
    finally:
        r.stop()
        assert r.join(2.0)


# -- the SLO rule ------------------------------------------------------------


def _ns(s):
    return int(s * 1e9)


def test_encode_resync_full_zero_rule_fires_and_resolves():
    """The invariant rule: any full re-encode RATE while load is offered
    fires exactly once and resolves exactly once; outside the active
    window (warmup fulls at encoder birth) it never fires."""
    rule = next(r for r in default_churn_rules()
                if r.name == "encode_resync_full_zero")
    assert rule.active_only and rule.op == "ceil" and rule.reduce == "rate"
    assert rule.threshold == 0.0
    assert 'encoder_resync_full_total{reason="window_exceeded"}' \
        in rule.series
    dog = SLOWatchdog([rule])
    # warmup fulls before the window opens: suppressed by active_only
    assert dog.observe(rule, 0.4, _ns(0), active=False) is None
    assert not dog.firing()
    # quiet run: a zero rate inside the window never fires
    assert dog.observe(rule, 0.0, _ns(5), active=True) is None
    # a full re-encode mid-window: ONE firing transition
    tr = dog.observe(rule, 0.1, _ns(10), active=True,
                     samples=[[_ns(10), 1.0]])
    assert tr is not None and tr["state"] == "firing"
    assert dog.firing() == ["encode_resync_full_zero"]
    # rate decays back to zero: ONE resolved transition
    tr = dog.observe(rule, 0.0, _ns(45), active=True)
    assert tr is not None and tr["state"] == "resolved"
    assert not dog.firing()
    assert [t["state"] for t in dog.transitions] == ["firing", "resolved"]


def test_default_churn_rules_include_slipstream():
    names = {r.name for r in default_churn_rules()}
    assert "encode_resync_full_zero" in names


# -- live e2e ----------------------------------------------------------------


N_NODES = 12
N_PODS = 384
WAVE = 128


def mk_cluster_node(i):
    return api.Node(
        metadata=api.ObjectMeta(name=f"n{i:03d}"),
        spec=api.NodeSpec(capacity={"cpu": Quantity("64"),
                                    "memory": Quantity("256Gi")}))


def mk_cluster_pod(i):
    return api.Pod(
        metadata=api.ObjectMeta(name=f"e{i:05d}", namespace="default",
                                uid=f"uid-e{i:05d}"),
        spec=api.PodSpec(containers=[api.Container(
            name="c", image="img",
            resources=api.ResourceRequirements(limits={
                "cpu": Quantity(f"{100 + (i % 8) * 100}m"),
                "memory": Quantity(f"{128 + (i % 4) * 64}Mi")}))]))


def test_e2e_mid_run_resync_zero_full(monkeypatch):
    """Live stack, the wave loop, KTPU_DEBUG replay gate armed: a
    mid-run resync (the delta cursor's journal reads fail until a replay
    lands, as a watch-window loss would) drains the full backlog with
    ZERO full re-encodes — every resync replays the journal."""
    monkeypatch.setattr(tpu_batch, "_DEBUG_REPLAY", True)
    sx = metrics.slipstream_metrics()
    m = Master()
    client = Client(InProcessTransport(m))
    for i in range(N_NODES):
        client.nodes().create(mk_cluster_node(i))
    for i in range(N_PODS):
        client.pods().create(mk_cluster_pod(i))
    factory = ConfigFactory(client)
    config = factory.create()
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        if len(factory.pod_queue.list()) >= N_PODS and \
                len(factory.node_store.list()) >= N_NODES:
            break
        time.sleep(0.02)
    else:
        pytest.fail("reflectors never synced the backlog")
    sched = BatchScheduler(config, factory, client, wave_size=WAVE,
                           wave_linger_s=0.02)
    modeler = config.modeler
    real_delta = modeler.delta
    replay_floor = sx.resync_replay.total()
    full_before = sx.resync_full.total()
    birth_before = sx.resync_full.value("no_checkpoint")

    def wounded_delta(token):
        # synchronous with the wave loop, so no timing window: once a
        # checkpoint exists, every journal read from the live cursor
        # fails (None = window lost) until one checkpoint-based replay
        # lands; the replay's own read — from the checkpoint token —
        # stays real. The encoder-birth wave (no checkpoint yet) is the
        # only full re-encode this run is allowed.
        if sx.resync_replay.total() == replay_floor and \
                sched._ckpt is not None and token != sched._ckpt[1]:
            return None
        return real_delta(token)

    modeler.delta = wounded_delta
    sched.run()
    try:
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            bound = sum(1 for p in client.pods().list().items
                        if p.spec.host)
            if bound >= N_PODS:
                break
            time.sleep(0.05)
        assert bound >= N_PODS, f"only {bound}/{N_PODS} bound"
        fulls = sx.resync_full.total() - full_before
        births = sx.resync_full.value("no_checkpoint") - birth_before
        assert fulls == births, \
            "a mid-run resync fell back to a full re-encode"
        assert sx.resync_replay.total() - replay_floor >= 1, \
            "injected journal loss never exercised the replay path"
    finally:
        sched.stop()
        factory.stop()
