"""What a configuration says its nodes, pods and services are.

The harness does not interpret a template. It reads the configuration's own
description here, once, and passes it on whole: to the API object on one
side (``feeder.PodFactory``, ``control_plane.make_nodes`` /
``make_services``) and to the plain reference on the other
(``correct.py``). Nothing in this file knows what a label, a port or a
resource means; it knows how many of each template there are and in which
order they come. It imports neither JAX nor numpy: the feeder reads it too.

Keys of a configuration (all optional; without them the one
``node_template`` / ``pod_template`` of the ``sched-basic-*`` files):

    node_templates  [{name, count, capacity{resource: quantity}, labels{}}]
                    ``nodes`` must equal the sum of the counts. Nodes
                    node-00000.. are dealt to the templates in list order,
                    so a node's template follows from its name, not the seed.
    pod_templates   [{name, weight, limits{}, labels{}, node_selector{},
                      host_ports[], namespace, in, container, image}]
                    ``weight`` a whole number above 0; ``in`` a list of
                    "warm" (the suite's init pods: warm-up rounds only) and
                    "window" (its measured pods), both by default.
    services        [{name, namespace, selector{}}]; absent or 0: none.
"""

from __future__ import annotations

import random

PHASES = ("warm", "window")
PLAN_BLOCK = 1000     # pods to a block of the plan, where the weights allow


class ConfigError(ValueError):
    """The configuration's description of its deployment does not hold
    together."""


def _each_once(what: str, names: list) -> None:
    if len(set(names)) != len(names):
        raise ConfigError(f"{what} names {names}: each once")


def node_templates(config: dict) -> list:
    nodes = int(config["nodes"])
    raw = config.get("node_templates") or \
        [dict(config["node_template"], count=nodes)]
    out = [{"name": t.get("name", "default"), "count": int(t["count"]),
            "capacity": dict(t["capacity"]),
            "labels": dict(t.get("labels", {}))} for t in raw]
    if any(t["count"] <= 0 for t in out) or \
            sum(t["count"] for t in out) != nodes:
        raise ConfigError(f"node_templates count "
                          f"{[t['count'] for t in out]}: each above 0 and "
                          f"{nodes} (nodes) together")
    _each_once("node template", [t["name"] for t in out])
    return out


def nodes_of(config: dict) -> dict:
    """{node name: its template}, in name order."""
    out, i = {}, 0
    for t in node_templates(config):
        for _ in range(t["count"]):
            out[f"node-{i:05d}"] = t
            i += 1
    return out


def pod_templates(config: dict) -> list:
    raw = config.get("pod_templates") or [config["pod_template"]]
    out = []
    for t in raw:
        phases = list(t.get("in", PHASES))
        weight = t.get("weight", 1)
        if not phases or set(phases) - set(PHASES):
            raise ConfigError(f"pod template in {phases}: of {PHASES}")
        if weight != int(weight) or weight <= 0:
            raise ConfigError(f"pod template weight {weight!r}: a whole "
                              f"number above 0")
        out.append({"name": t.get("name", "default"), "weight": int(weight),
                    "limits": dict(t["limits"]),
                    "labels": dict(t.get("labels", {})),
                    "node_selector": dict(t.get("node_selector", {})),
                    "host_ports": [int(p) for p in t.get("host_ports", [])],
                    "namespace": t.get("namespace", config["namespace"]),
                    "in": phases,
                    "container": t.get("container", "pause"),
                    "image": t.get("image", "pause")})
    _each_once("pod template", [t["name"] for t in out])
    return out


def services(config: dict) -> list:
    return [{"name": s["name"],
             "namespace": s.get("namespace", config["namespace"]),
             "selector": dict(s["selector"])}
            for s in config.get("services") or []]


def pod_plan(templates: list, phase: str, seed: int, count: int) -> list:
    """The template (by index) of each of a phase's first ``count`` pods,
    drawn here and never on the send path. Blocks of one fixed length, each
    holding every template of the phase in proportion to its weight,
    shuffled within the block: every seed offers the same multiset, block
    by block, in another order."""
    weights = [(i, t["weight"]) for i, t in enumerate(templates)
               if phase in t["in"]]
    if not weights:
        raise ConfigError(f"no pod template is in {phase!r}")
    total = sum(w for _, w in weights)
    block = [i for i, w in weights
             for _ in range(w * max(1, PLAN_BLOCK // total))]
    rng = random.Random(f"pod-plan/{phase}/{seed}")
    out: list = []
    while len(out) < count:
        rng.shuffle(block)
        out += block
    return out
