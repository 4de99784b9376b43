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
    scheduler       {provider, policy, ...}: the scheduler the deployment
                    runs, as ``kube-scheduler --algorithm-provider`` and
                    ``--policy-config-file`` state it. ``provider`` is
                    "DefaultProvider" when absent; ``policy``, when
                    present, is a Policy object inline, in exactly the JSON
                    format that the policy file holds:
                      {"predicates": [{name, argument?}],
                       "priorities": [{name, weight?, argument?}]}
                    a predicate's ``argument`` {"serviceAffinity":
                    {labels[]}} or {"labelsPresence": {labels[], presence}},
                    a priority's {"serviceAntiAffinity": {label}} or
                    {"labelPreference": {label, presence}}; an entry
                    without one names a plugin of the vocabulary below. A
                    Policy replaces the provider's sets whole. The other
                    keys of the block (wave size, linger) are what the
                    program's defaults are, written down and read by
                    nothing.
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


PROVIDERS = ("DefaultProvider",)
# the Policy vocabulary (upstream factory/plugins.go, defaults.go and
# api/types.go): plugins by name, and the argument kinds that make an entry
# of any name a plugin of their own
PREDICATES = ("PodFitsPorts", "PodFitsResources", "NoDiskConflict",
              "MatchNodeSelector", "HostName", "Schedulable")
PRIORITIES = ("LeastRequestedPriority", "ServiceSpreadingPriority",
              "EqualPriority")
PREDICATE_ARGUMENTS = {"serviceAffinity": {"labels": list},
                       "labelsPresence": {"labels": list, "presence": bool}}
PRIORITY_ARGUMENTS = {"serviceAntiAffinity": {"label": str},
                      "labelPreference": {"label": str, "presence": bool}}


def _policy_entry(kind: str, entry, names: tuple, arguments: dict) -> None:
    if not isinstance(entry, dict) or not isinstance(entry.get("name"), str):
        raise ConfigError(f"policy {kind} {entry!r}: an object with a name")
    allowed = {"name", "argument", "weight"} if kind == "priority" \
        else {"name", "argument"}
    extra = set(entry) - allowed
    if extra:
        raise ConfigError(f"policy {kind} {entry['name']!r} has "
                          f"{sorted(extra)}")
    if kind == "priority" and type(entry.get("weight", 1)) is not int:
        raise ConfigError(f"policy priority {entry['name']!r} weight "
                          f"{entry.get('weight')!r}: a whole number")
    argument = entry.get("argument")
    if argument is None:
        if entry["name"] not in names:
            raise ConfigError(f"policy {kind} {entry['name']!r} is not in the "
                              f"vocabulary {list(names)} and has no argument "
                              f"of {list(arguments)}")
        return
    if not isinstance(argument, dict) or len(argument) != 1 or \
            next(iter(argument)) not in arguments:
        raise ConfigError(f"policy {kind} {entry['name']!r} argument "
                          f"{argument!r}: one of {list(arguments)}")
    (what, value), = argument.items()
    types = arguments[what]
    sound = isinstance(value, dict) and not set(value) - set(types) and \
        all(isinstance(value[k], t) for k, t in types.items() if k in value)
    if sound and "labels" in value:
        sound = all(isinstance(label, str) for label in value["labels"])
    if not sound:
        raise ConfigError(f"policy {kind} {entry['name']!r} {what} "
                          f"{value!r}: keys of "
                          f"{ {k: t.__name__ for k, t in types.items()} }")


def scheduler(config: dict) -> dict:
    """{"provider": name, "policy": the Policy object or None}, as the
    configuration states them; a plugin outside the vocabulary, or a
    provider there is none of, is a ConfigError."""
    block = config.get("scheduler") or {}
    provider = block.get("provider", PROVIDERS[0])
    if provider not in PROVIDERS:
        raise ConfigError(f"scheduler provider {provider!r}: of {PROVIDERS}")
    policy = block.get("policy")
    if policy is None:
        return {"provider": provider, "policy": None}
    if not isinstance(policy, dict) or \
            set(policy) - {"kind", "apiVersion", "predicates", "priorities"}:
        raise ConfigError(f"scheduler policy {policy!r}: a Policy object")
    for key, kind, names, arguments in (
            ("predicates", "predicate", PREDICATES, PREDICATE_ARGUMENTS),
            ("priorities", "priority", PRIORITIES, PRIORITY_ARGUMENTS)):
        entries = policy.get(key, [])
        if not isinstance(entries, list):
            raise ConfigError(f"scheduler policy {key} {entries!r}: a list")
        for entry in entries:
            _policy_entry(kind, entry, names, arguments)
    return {"provider": provider, "policy": policy}


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
