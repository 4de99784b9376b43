"""The comparison that decides ``correct``.

What the timed path produced — the decisions of the timed waves themselves,
at the timed sizes, every one of them — is held against the configuration's
plain reference (``benchmarks/references/<name>.py``), replayed from the
empty cluster through every wave in order: the cluster before a wave is the
commits of the earlier waves. Beside it, the guarantees the configuration
states are held against one final LIST and the client's own watch.

Each number compared has a limit of its own, and every comparison here is
exact: the limit is 0. ``numbers`` maps a short name to (value, limit).
"""

from __future__ import annotations

import importlib

from benchmarks.harness import deployment as dep


def load_reference(config: dict):
    return importlib.import_module(
        f"benchmarks.references.{config['reference']}")


def replay(ref, config: dict, waves: list, pod_of: dict, solve=None) -> list:
    """The reference's (host, score) for every pod of every wave.
    ``pod_of``: {pod name: (uid, its pod template)}; the nodes, the
    services and the scheduler's Policy, where it states one, are the
    configuration's own, each as it states them."""
    policy = dep.scheduler(config)["policy"]
    stated = {} if policy is None else {"policy": policy}
    cluster = ref.Cluster(dep.nodes_of(config), dep.services(config),
                          **stated)
    solve = solve or ref.solve_wave
    return [solve(cluster, [pod_of[name] for name in w["pods"]])
            for w in waves]


def _pod_of(feeder_doc: dict) -> dict:
    templates = feeder_doc["pod_templates"]
    return {row[0]: (row[1] or "", templates[row[7]])
            for row in feeder_doc["pods"]}


def _known_waves(waves: list, created) -> tuple:
    """The waves with pods the feeder never made taken out, and how many
    such pods there were."""
    foreign = sum(1 for w in waves for n in w["pods"] if n not in created)
    known = [dict(w, pods=[n for n in w["pods"] if n in created])
             for w in waves] if foreign else waves
    return known, foreign


def compare(config: dict, feeder_doc: dict, waves: list, listed: dict,
            programs: dict, events: dict, kernel_program: str) -> dict:
    ref = load_reference(config)
    created = {}          # name -> (uid, client's host, error)
    for name, uid, _phase, host, _bound_t, error, rebound, _template in \
            feeder_doc["pods"]:
        created[name] = (uid, host, error, rebound)

    known, foreign = _known_waves(waves, created)
    expected = replay(ref, config, known, _pod_of(feeder_doc))

    decisions_differ = scores_differ = 0
    decided: dict = {}
    decided_twice = 0
    first_diff = None
    for w, exp in zip(waves, expected):
        names = [n for n in w["pods"] if n in created]
        got = {n: (h, s) for n, h, s in zip(w["pods"], w["hosts"],
                                            w["scores"])}
        for name, (host, score) in zip(names, exp):
            ghost, gscore = got[name]
            if ghost != host:
                decisions_differ += 1
                first_diff = first_diff or (name, ghost, host)
            elif host is not None and gscore != score:
                scores_differ += 1
            if ghost is not None:
                decided_twice += name in decided
                decided[name] = ghost

    where = listed["where"]
    bound_elsewhere = never_bound = rebound = 0
    for name, (_uid, seen_host, error, rebound_to) in created.items():
        if error:
            continue
        rebound += rebound_to is not None
        final = where.get(name)
        if seen_host is None or final is None:
            never_bound += 1
            continue
        if final != seen_host or decided.get(name) != final:
            bound_elsewhere += 1
    off_kernel = sum(n for prog, n in programs.items()
                     if prog != kernel_program)
    other_events = sum(n for reason, n in events.items()
                       if reason != "Scheduled")
    numbers = {
        "decisions_differ": (decisions_differ, 0),
        "scores_differ": (scores_differ, 0),
        "decided_twice": (decided_twice, 0),
        "bound_elsewhere": (bound_elsewhere + rebound, 0),
        "never_bound": (never_bound, 0),
        "foreign_pods": (foreign, 0),
        "nodes_over_capacity": (listed["nodes_over_capacity"], 0),
        "bad_listing": (listed["bound_to_unknown_node"]
                        + listed["listed_twice"]
                        + listed["host_port_clashes"], 0),
        "waves_off_kernel": (off_kernel, 0),
        "other_events": (other_events, 0),
    }
    return {"numbers": numbers, "first_diff": first_diff,
            "compared_pods": sum(len(w["pods"]) for w in waves),
            "compared_waves": len(waves)}


def control_reading(config: dict, feeder_doc: dict, waves: list) -> int:
    """``decisions_differ`` with the CONTROL in the program's place: the
    reference with the in-wave commit put off, over the same waves, held
    against the reference."""
    ref = load_reference(config)
    pod_of = _pod_of(feeder_doc)
    known, _ = _known_waves(waves, pod_of)
    expected = replay(ref, config, known, pod_of)
    control = replay(ref, config, known, pod_of,
                     solve=ref.solve_wave_uncommitted)
    return sum(1 for e, c in zip(expected, control)
               for (eh, _), (ch, _) in zip(e, c) if eh != ch)


def is_correct(numbers: dict) -> bool:
    return all(value <= limit for value, limit in numbers.values())
