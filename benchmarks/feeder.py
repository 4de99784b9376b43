#!/usr/bin/env python3
"""The load generator: a child process that never imports JAX.

Started by ``run.py`` once the control plane is up. It makes its pods from
``--seed``, creates them over HTTP against the apiserver, watches ``pods``
for ``spec.host`` becoming set, stamps both ends with ``time.monotonic()``
(one clock for parent and child on Linux) and writes one JSON document to
``--out``. The parent owns the window and speaks over stdin, one word a
line; the child answers on stdout:

    warm N -> one warm-up round: N pods created          -> "created"
              and, once every one of them is seen bound   -> "warmed"
    open   -> the traffic starts                                -> "opened"
    close  -> the traffic stops; pods still unbound are waited for
              (``--drain-s``), the document is written          -> "done"

One general generator reads every traffic file:

    loop "closed": keep ``in_flight`` pods created-and-not-yet-seen-bound,
                   topping up as bindings arrive
    loop "open"  : one pod at each due time, ``rate`` a second; a create is
                   timed from when it was DUE. ``arrivals`` "uniform" spaces
                   them evenly; "exponential" draws the gaps of each block of
                   ``arrival_block`` pods from the same fixed set (the
                   exponential's quantiles) in an order shuffled by the seed,
                   so every seed offers the same arrivals in another order.

Which template a pod takes (``harness/deployment.py``: ``pod_templates``) is
the pod plan's to say, drawn from the seed before the window opens: blocks
that each hold every template in proportion to its weight, so every seed
offers the same mix in another order.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import queue
import random
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.harness import deployment as dep     # noqa: E402
from kubernetes_tpu.api import types as api          # noqa: E402
from kubernetes_tpu.api.quantity import Quantity     # noqa: E402
from kubernetes_tpu.client.client import Client      # noqa: E402
from kubernetes_tpu.client.http import HTTPTransport  # noqa: E402

BOUND_FILTER = "spec.host!="


def percentile(values: list, q: float) -> float:
    """The q-quantile (0..1) by linear interpolation between order
    statistics; +inf entries (pods never bound) sort last."""
    if not values:
        raise ValueError("no values")
    s = sorted(values)
    pos = q * (len(s) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(s) - 1)
    if math.isinf(s[lo]) or (math.isinf(s[hi]) and pos > lo):
        return math.inf
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def open_loop_schedule(rate: float, count: int, arrivals: str, seed: int,
                       block: int = 1000) -> list:
    """Offsets in seconds from the window's opening at which the open loop's
    pods are due. Every seed gets the same multiset of gaps per block."""
    if rate <= 0:
        raise ValueError("rate must be above 0")
    if arrivals == "uniform":
        return [i / rate for i in range(count)]
    if arrivals != "exponential":
        raise ValueError(f"arrivals {arrivals!r}: uniform or exponential")
    gaps = [-math.log(1.0 - (k + 0.5) / block) / rate for k in range(block)]
    rng = random.Random(seed)
    out, t = [], 0.0
    while len(out) < count:
        order = gaps[:]
        rng.shuffle(order)
        for g in order:
            t += g
            out.append(t)
    return out[:count]


class PodFactory:
    """Pods of the configuration's templates, each taken as the plan of its
    phase says; the seed varies what the source leaves free: names and uids
    (the uid feeds the scheduler's tie-break) and the order of the plan."""

    def __init__(self, templates: list, plans: dict, seed: int):
        self.templates = templates
        self.plans = plans            # phase -> template index of each pod
        self.tag = f"{seed & 0xFFFFFFFFFF:x}"
        self._n = 0
        self._made = dict.fromkeys(plans, 0)
        self._lock = threading.Lock()

    def make(self, phase: str = "window") -> tuple:
        """(template index, pod): the phase's next pod."""
        with self._lock:
            i = self._n
            self._n += 1
            plan = self.plans[phase]
            index = plan[self._made[phase] % len(plan)]
            self._made[phase] += 1
        template = self.templates[index]
        name = f"p{self.tag}-{i:07d}"
        limits = {k: Quantity(str(v))
                  for k, v in template["limits"].items()}
        return index, api.Pod(
            metadata=api.ObjectMeta(name=name,
                                    namespace=template["namespace"],
                                    uid=f"uid-{name}",
                                    labels=dict(template["labels"])),
            spec=api.PodSpec(
                node_selector=dict(template["node_selector"]),
                containers=[api.Container(
                    name=template["container"], image=template["image"],
                    ports=[api.ContainerPort(host_port=p, container_port=p)
                           for p in template["host_ports"]],
                    resources=api.ResourceRequirements(limits=limits))]))


class Feeder:
    def __init__(self, base_url: str, namespaces: list, factory: PodFactory,
                 threads: int):
        self.base_url = base_url
        self.namespaces = namespaces      # every one a template names
        self.factory = factory
        self.threads = threads
        self.lock = threading.Lock()
        self.pods: dict = {}          # name -> record
        self.order: list = []         # names in creation order
        self.bound_cond = threading.Condition(self.lock)
        self.unbound = 0              # created OK and not yet seen bound
        self.on_bound = None          # closed loop: releases a slot
        self.watch_relists = 0
        self.stopping = threading.Event()
        self._watchers: dict = {}     # namespace -> its open watch
        self._warm_clients = None

    # -- the client's watch -------------------------------------------------
    def start_watch(self) -> None:
        self._client = Client(HTTPTransport(self.base_url,
                                            user_agent="bench-feeder-watch"))
        for namespace in self.namespaces:
            threading.Thread(target=self._watch_loop, args=(namespace,),
                             name="feeder-watch", daemon=True).start()

    def stop_watch(self) -> None:
        self.stopping.set()
        for watcher in list(self._watchers.values()):
            watcher.stop()

    def _watch_loop(self, namespace: str) -> None:
        pods = self._client.pods(namespace)
        while not self.stopping.is_set():
            try:
                listed = pods.list(field_selector=BOUND_FILTER)
                for p in listed.items:
                    self._saw_bound(p.metadata.name, p.spec.host)
                watcher = self._watchers[namespace] = pods.watch(
                    field_selector=BOUND_FILTER,
                    resource_version=listed.metadata.resource_version)
                for ev in watcher:
                    obj = ev.object
                    if ev.type == "ERROR" or not hasattr(obj, "spec"):
                        break
                    if ev.type != "DELETED" and obj.spec.host:
                        self._saw_bound(obj.metadata.name, obj.spec.host)
            except Exception as e:  # noqa: BLE001 — relist and go on
                if self.stopping.is_set():
                    return
                print(f"feeder: watch failed ({e!r}); relisting",
                      file=sys.stderr, flush=True)
                time.sleep(0.05)
            if not self.stopping.is_set():
                with self.lock:
                    self.watch_relists += 1

    def _saw_bound(self, name: str, host: str) -> None:
        now = time.monotonic()
        with self.lock:
            rec = self.pods.get(name)
            if rec is None:
                # the watch can beat the create's own response
                rec = self.pods[name] = {"early_host": host, "early_t": now}
                return
            if "bound_t" in rec or "created_t" not in rec:
                if rec.get("host", host) != host:
                    rec["rebound_to"] = host
                return
            rec["bound_t"], rec["host"] = now, host
            self.unbound -= 1
            self.bound_cond.notify_all()
        if self.on_bound is not None:
            self.on_bound()

    # -- creating -----------------------------------------------------------
    def create(self, client: Client, phase: str, due_t=None) -> None:
        template, pod = self.factory.make(phase)
        name = pod.metadata.name
        sent = time.monotonic()
        try:
            client.pods(pod.metadata.namespace).create(pod)
        except Exception as e:  # noqa: BLE001 — counted, reported
            with self.lock:
                self.pods[name] = {"phase": phase, "template": template,
                                   "sent_t": sent,
                                   "due_t": due_t, "error": repr(e)[:200]}
                self.order.append(name)
            if self.on_bound is not None:
                self.on_bound()
            return
        done = time.monotonic()
        with self.lock:
            early = self.pods.get(name) or {}
            rec = self.pods[name] = {
                "phase": phase, "template": template,
                "uid": pod.metadata.uid, "sent_t": sent,
                "due_t": due_t, "created_t": done}
            self.order.append(name)
            self.unbound += 1
            if "early_host" in early:
                rec["bound_t"], rec["host"] = early["early_t"], \
                    early["early_host"]
                self.unbound -= 1
                self.bound_cond.notify_all()
        if "early_host" in early and self.on_bound is not None:
            self.on_bound()

    def wait_all_bound(self, timeout_s: float) -> bool:
        deadline = time.monotonic() + timeout_s
        with self.lock:
            while self.unbound > 0:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self.bound_cond.wait(min(left, 0.5))
        return True

    def _clients(self) -> list:
        return [Client(HTTPTransport(self.base_url,
                                     user_agent=f"bench-feeder-{i}"))
                for i in range(self.threads)]

    def warm_round(self, n: int) -> None:
        """One warm-up round: ``n`` pods created, from every thread."""
        if self._warm_clients is None:
            self._warm_clients = self._clients()
        parts = [range(f, n, self.threads) for f in range(self.threads)]
        ts = [threading.Thread(
            target=lambda c=c, part=part: [self.create(c, "warm")
                                           for _ in part])
            for c, part in zip(self._warm_clients, parts) if len(part)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()

    def run_closed(self, in_flight: int, stop: threading.Event) -> list:
        slots = threading.Semaphore(in_flight)
        self.on_bound = slots.release

        def sender(client):
            while not stop.is_set():
                if slots.acquire(timeout=0.05):
                    if stop.is_set():
                        return
                    self.create(client, "window")

        ts = [threading.Thread(target=sender, args=(c,), daemon=True,
                               name=f"feeder-{i}")
              for i, c in enumerate(self._clients())]
        for t in ts:
            t.start()
        return ts

    def run_open(self, offsets: list, stop: threading.Event) -> list:
        due: "queue.Queue" = queue.Queue()
        t0 = time.monotonic()

        def clock():
            for off in offsets:
                wait = t0 + off - time.monotonic()
                if wait > 0 and stop.wait(wait):
                    return
                if stop.is_set():
                    return
                due.put(t0 + off)

        def sender(client):
            while not stop.is_set():
                try:
                    d = due.get(timeout=0.05)
                except queue.Empty:
                    continue
                self.create(client, "window", due_t=d)

        ts = [threading.Thread(target=clock, daemon=True, name="feeder-clock")]
        ts += [threading.Thread(target=sender, args=(c,), daemon=True,
                                name=f"feeder-{i}")
               for i, c in enumerate(self._clients())]
        for t in ts:
            t.start()
        return ts


def _offered_t(rec: dict) -> float:
    """When a pod was offered: its due time in the open loop, else when its
    create was sent."""
    return rec["due_t"] if rec.get("due_t") is not None else rec["sent_t"]


def summarize(pods: dict, order: list, open_t: float, close_t: float,
              loop: str, names: tuple = ("default",)) -> dict:
    """The end-to-end numbers, from the client's stamps alone. Latency is
    taken over EVERY pod created in the window: bound seen - create sent
    (closed loop) or - create DUE (open loop); one never seen bound, or
    whose create failed, is +inf and counts in ``failed``. ``by_template``
    (keyed by ``names``) is the mix that was really offered."""
    window = [pods[n] for n in order if pods[n].get("phase") == "window"
              and open_t <= _offered_t(pods[n]) <= close_t]
    lat, late, create = [], [], []
    failed = 0
    by_template = {n: {"attempted": 0, "bound": 0} for n in names}
    for r in window:
        mix = by_template[names[r.get("template", 0)]]
        mix["attempted"] += 1
        mix["bound"] += "bound_t" in r
        start = _offered_t(r) if loop == "open" else r["sent_t"]
        if r.get("due_t") is not None:
            late.append(r["sent_t"] - r["due_t"])
        if "created_t" in r:
            create.append(r["created_t"] - r["sent_t"])
        if "bound_t" in r:
            lat.append(r["bound_t"] - start)
        else:
            failed += 1
            lat.append(math.inf)
    bound_in_window = sum(1 for r in pods.values()
                          if "bound_t" in r and r.get("phase") == "window"
                          and open_t <= r["bound_t"] <= close_t)

    def backlog(at: float) -> int:
        """Pods offered by ``at`` and not yet seen bound by then."""
        return sum(1 for r in window if _offered_t(r) <= at
                   and not r.get("bound_t", math.inf) <= at)

    half_t = (open_t + close_t) / 2
    out = {"attempted": len(window), "failed": failed,
           "backlog_half": backlog(half_t), "backlog_close": backlog(close_t),
           "bound_in_window": bound_in_window,
           "window_s": close_t - open_t,
           "pods_per_s": bound_in_window / (close_t - open_t),
           "by_template": by_template}
    if lat:
        out["bound_p50_s"] = percentile(lat, 0.50)
        out["bound_p90_s"] = percentile(lat, 0.90)
        out["bound_p99_s"] = percentile(lat, 0.99)
    if create:
        out["create_p99_s"] = percentile(create, 0.99)
        out["create_max_s"] = max(create)
    if late:
        out["late_p99_s"] = percentile(late, 0.99)
        out["late_max_s"] = max(late)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--base-url", required=True)
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--max-seconds", type=float, default=60.0)
    ap.add_argument("--drain-s", type=float, default=60.0)
    ap.add_argument("--warm-timeout-s", type=float, default=900.0)
    args = ap.parse_args(argv)
    if "jax" in sys.modules:
        print("feeder: jax was imported; the load generator must not hold "
              "the chip", file=sys.stderr)
        return 1
    with open(args.config) as f:
        config = json.load(f)
    with open(args.traffic) as f:
        traffic = json.load(f)

    templates = dep.pod_templates(config)
    rate = float(traffic.get("rate", 0))
    # the window's plan: an open loop's every due pod; a closed loop makes
    # as many as are bound, so its plan is long and comes round again
    planned = {"warm": sum(int(n) for n in traffic["warm_rounds"]),
               "window": int(rate * args.max_seconds) + 1
               if traffic["loop"] == "open" else 64 * dep.PLAN_BLOCK}
    plans = {phase: dep.pod_plan(templates, phase, args.seed, count)
             for phase, count in planned.items() if count}
    feeder = Feeder(args.base_url,
                    sorted({t["namespace"] for t in templates}),
                    PodFactory(templates, plans, args.seed),
                    int(traffic["feeders"]))
    feeder.start_watch()
    stop = threading.Event()
    open_t = close_t = None
    threads: list = []
    for line in sys.stdin:
        word = line.strip()
        if word.startswith("warm "):
            n = int(word.split()[1])
            feeder.warm_round(n)
            print("created", flush=True)
            if not feeder.wait_all_bound(args.warm_timeout_s):
                print(f"feeder: warm-up round of {n} pods was not bound "
                      f"within {args.warm_timeout_s:.0f}s", file=sys.stderr)
                return 1
            print("warmed", flush=True)
        elif word == "open":
            open_t = time.monotonic()
            if traffic["loop"] == "closed":
                threads = feeder.run_closed(int(traffic["in_flight"]), stop)
            elif traffic["loop"] == "open":
                offsets = open_loop_schedule(
                    rate, planned["window"],
                    traffic.get("arrivals", "uniform"), args.seed,
                    int(traffic.get("arrival_block", 1000)))
                threads = feeder.run_open(offsets, stop)
            else:
                print(f"feeder: loop {traffic['loop']!r}: closed or open",
                      file=sys.stderr)
                return 1
            print(f"opened {open_t!r}", flush=True)
        elif word == "close":
            stop.set()
            close_t = time.monotonic()
            for t in threads:
                t.join(timeout=30.0)
            drained = feeder.wait_all_bound(args.drain_s)
            drain_s = time.monotonic() - close_t
            break
    else:
        return 1
    if open_t is None:
        return 1
    feeder.stop_watch()
    with feeder.lock:
        pods = {n: r for n, r in feeder.pods.items() if "phase" in r}
        order = list(feeder.order)
    doc = {"seed": args.seed, "loop": traffic["loop"],
           "open_t": open_t, "close_t": close_t, "drained": drained,
           "drain_s": drain_s, "watch_relists": feeder.watch_relists,
           "summary": summarize(pods, order, open_t, close_t,
                                traffic["loop"],
                                tuple(t["name"] for t in templates)),
           "pod_templates": templates,
           "pods": [[n, pods[n].get("uid"), pods[n].get("phase"),
                     pods[n].get("host"), pods[n].get("bound_t"),
                     pods[n].get("error"), pods[n].get("rebound_to"),
                     pods[n].get("template")]
                    for n in order]}
    tmp = args.out + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, args.out)
    print("done", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
