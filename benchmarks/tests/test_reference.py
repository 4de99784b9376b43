"""The plain reference against upstream's rule as the repository's serial
oracle has it (a second witness; the reference itself imports nothing of
the program), and the control, which has to come out as not correct."""

import pytest

from benchmarks.harness import correct as cor
from benchmarks.references import serial_resources as ref

CONFIG = {"nodes": 12, "reference": "serial_resources",
          "node_template": {"capacity": {"cpu": "4", "memory": "32Gi"}}}
LIMITS = {"cpu": "100m", "memory": "500Mi"}
TEMPLATE = {"name": "default", "namespace": "default", "limits": LIMITS}


def test_quantities():
    assert ref.milli("4") == 4000 and ref.milli("100m") == 100
    assert ref.whole("32Gi") == 32 * 2 ** 30
    assert ref.whole("500Mi") == 500 * 2 ** 20 and ref.whole("7") == 7


def test_fnv1a64_known_values():
    assert ref.fnv1a64("") == 0xCBF29CE484222325
    assert ref.fnv1a64("a") == 0xAF63DC4C8601EC8C


def _waves(n_pods, sizes):
    names = [f"pod-{i:04d}" for i in range(n_pods)]
    waves, at = [], 0
    while at < n_pods:
        size = sizes[len(waves) % len(sizes)]
        waves.append({"pods": names[at:at + size]})
        at += size
    return names, waves


def test_agrees_with_the_serial_oracle_until_the_cluster_is_full():
    from kubernetes_tpu.api import types as api
    from kubernetes_tpu.api.quantity import Quantity
    from kubernetes_tpu.models.oracle import solve_serial

    n_pods = 12 * 40 + 5                     # five more than fit
    names, waves = _waves(n_pods, [n_pods])
    uid_of = {n: f"uid-{n}" for n in names}
    nodes = [api.Node(metadata=api.ObjectMeta(name=f"node-{i:05d}"),
                      spec=api.NodeSpec(capacity={
                          "cpu": Quantity("4"), "memory": Quantity("32Gi")}))
             for i in range(12)]
    pods = [api.Pod(
        metadata=api.ObjectMeta(name=n, namespace="default", uid=uid_of[n]),
        spec=api.PodSpec(containers=[api.Container(
            name="c", image="i", resources=api.ResourceRequirements(
                limits={k: Quantity(v) for k, v in LIMITS.items()}))]))
        for n in names]
    want = solve_serial(nodes, [], pods)
    got = cor.replay(ref, CONFIG, waves,
                     {n: (uid_of[n], TEMPLATE) for n in names})[0]
    assert [h for h, _ in got] == want
    assert want[-5:] == [None] * 5 and None not in want[:-5]


def _run_doc(names):
    return {"pod_templates": [TEMPLATE],
            "pods": [[n, f"uid-{n}", "window", None, 1.0, None, None, 0]
                     for n in names]}


def _pod_of(names):
    return {n: (f"uid-{n}", TEMPLATE) for n in names}


def _as_recorded(waves, solved):
    return [dict(w, hosts=[h for h, _ in s], scores=[c for _, c in s])
            for w, s in zip(waves, solved)]


def test_the_control_comes_out_as_not_correct():
    """The reference with the in-wave commit put off, in the program's
    place: its decisions differ from the reference's, `correct` is false.
    The reference itself in the program's place is correct."""
    names, waves = _waves(400, [1, 2, 4, 64])
    doc = _run_doc(names)
    sound = _as_recorded(waves, cor.replay(ref, CONFIG, waves,
                                           _pod_of(names)))
    control = _as_recorded(waves, cor.replay(
        ref, CONFIG, waves, _pod_of(names),
        solve=ref.solve_wave_uncommitted))

    def verdict(recorded):
        where = {n: h for w in recorded for n, h in zip(w["pods"],
                                                        w["hosts"])}
        for row in doc["pods"]:
            row[3] = where[row[0]]
        listed = {"where": where, "nodes_over_capacity": 0,
                  "bound_to_unknown_node": 0, "listed_twice": 0,
                  "host_port_clashes": 0}
        return cor.compare(CONFIG, doc, recorded, listed,
                           {"pallas@tpu": len(recorded)},
                           {"Scheduled": 400}, "pallas@tpu")["numbers"]

    assert cor.is_correct(verdict(sound))
    numbers = verdict(control)
    assert numbers["decisions_differ"][0] >= 30
    assert not cor.is_correct(numbers)
    assert cor.control_reading(CONFIG, doc, sound) == \
        numbers["decisions_differ"][0]


@pytest.mark.parametrize("fault,number", [
    ("a pod bound elsewhere than decided", "bound_elsewhere"),
    ("a pod never bound", "never_bound"),
    ("a wave that left the kernel", "waves_off_kernel"),
    ("a failed-scheduling event", "other_events"),
    ("a node over capacity", "nodes_over_capacity")])
def test_each_guarantee_has_a_number_that_fails(fault, number):
    names, waves = _waves(50, [10])
    doc = _run_doc(names)
    rec = _as_recorded(waves, cor.replay(ref, CONFIG, waves, _pod_of(names)))
    where = {n: h for w in rec for n, h in zip(w["pods"], w["hosts"])}
    for row in doc["pods"]:
        row[3] = where[row[0]]
    listed = {"where": dict(where), "nodes_over_capacity": 0,
              "bound_to_unknown_node": 0, "listed_twice": 0,
              "host_port_clashes": 0}
    programs, events = {"pallas@tpu": 5}, {"Scheduled": 50}
    if number == "bound_elsewhere":
        listed["where"][names[3]] = "node-00011" \
            if where[names[3]] != "node-00011" else "node-00010"
    elif number == "never_bound":
        listed["where"][names[3]] = None
    elif number == "waves_off_kernel":
        programs = {"pallas@tpu": 4, "scan@tpu": 1}
    elif number == "other_events":
        events["FailedScheduling"] = 1
    else:
        listed["nodes_over_capacity"] = 1
    numbers = cor.compare(CONFIG, doc, rec, listed, programs, events,
                          "pallas@tpu")["numbers"]
    assert numbers[number][0] == 1 and not cor.is_correct(numbers)
    assert sum(v for v, _ in numbers.values()) == 1
