"""util/interpprobe.py: one thread a process sleeps a fixed period and
observes how late it ran again (``process_interpreter_handoff_seconds``),
and a render reads the kernel's context switches
(``process_context_switches_total``). The probe is process-wide, so every
test starts from a process without it and puts back what it found.
"""

import sys
import threading
import time

import pytest

from kubernetes_tpu.util import interpprobe, metrics, tracing

SERIES = "process_interpreter_handoff_seconds"


def samples() -> dict:
    """The default registry's text, as /metrics renders it: sample -> value."""
    out = {}
    for line in metrics.default_registry().render_text().splitlines():
        if not line.startswith("#"):
            sample, _, value = line.rpartition(" ")
            out[sample] = float(value)
    return out


def observed(before: dict, after: dict) -> tuple:
    """(count, mean seconds, share of the samples that were under 1 ms) of
    what the probe observed between two renders."""
    def grew(sample):
        return after.get(sample, 0.0) - before.get(sample, 0.0)
    count = grew(SERIES + "_count")
    return (count, grew(SERIES + "_sum") / count,
            grew(SERIES + '_bucket{le="0.001"}') / count)


def probes() -> list:
    return [t for t in threading.enumerate() if t.name == "interp-probe"]


@pytest.fixture(autouse=True)
def fresh_probe():
    """Other modules of this worker may have started an APIServer: stop
    the probe for the test and put it back after."""
    was_running = bool(probes())
    interpprobe.reset()
    yield
    interpprobe.reset()
    if was_running:
        interpprobe.ensure()


def test_an_idle_process_reads_the_timers_slack():
    before = samples()
    interpprobe.ensure()
    time.sleep(0.5)
    count, _mean, under_1ms = observed(before, samples())
    # 100 samples a second, and nobody wants the interpreter: the median
    # is the timer's own slack (the mean would take one stall of a shared
    # machine for the probe's reading)
    assert 25 <= count <= 55
    assert under_1ms > 0.5


def test_beside_spinning_threads_it_reads_the_switch_interval():
    stop = threading.Event()

    def spin():
        n = 0
        while not stop.is_set():
            n += 1

    spinners = [threading.Thread(target=spin, daemon=True) for _ in range(2)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(0.005)        # the default, whatever the run set
    try:
        for t in spinners:
            t.start()
        before = samples()
        interpprobe.ensure()
        time.sleep(0.8)
        count, mean, under_1ms = observed(before, samples())
    finally:
        stop.set()
        sys.setswitchinterval(interval)
        for t in spinners:
            t.join(timeout=5.0)
    assert not any(t.is_alive() for t in spinners)
    # a holder lets go only when asked, a switch interval after the probe
    # became runnable, and the other spinner may be handed the lock first
    assert count >= 5
    assert mean > 0.002
    assert under_1ms < 0.5


def test_ensure_twice_starts_one_thread():
    interpprobe.ensure()
    interpprobe.ensure()
    assert len(probes()) == 1
    racers = [threading.Thread(target=interpprobe.ensure) for _ in range(16)]
    for t in racers:
        t.start()
    for t in racers:
        t.join(timeout=5.0)
    assert len(probes()) == 1
    assert probes()[0].daemon


def test_reset_stops_the_thread_and_ensure_starts_another():
    interpprobe.ensure()
    first = probes()[0]
    interpprobe.reset()
    assert not first.is_alive()
    assert probes() == []
    interpprobe.reset()                 # nothing to stop: no error
    interpprobe.ensure()
    assert len(probes()) == 1 and probes()[0] is not first


def test_the_probes_cpu_is_its_own_roles_not_others():
    """Where the CPU clock is sampled on the timer's own tick a sleeper
    is charged ticks it never ran; they must not read as ``other``."""
    interpprobe.ensure()
    time.sleep(0.05)
    assert "interp_probe" in tracing.role_cpu_seconds()
    interpprobe.reset()                 # banked when the thread ends
    assert tracing.role_cpu_seconds()["interp_probe"] >= 0.0
    assert 'role="interp_probe"' in metrics.default_registry().render_text()


def test_context_switches_are_the_kernels_by_kind():
    before = samples()
    for _ in range(20):
        time.sleep(0.001)               # each sleep leaves the core once
    after = samples()
    voluntary = 'process_context_switches_total{kind="voluntary"}'
    involuntary = 'process_context_switches_total{kind="involuntary"}'
    assert after[voluntary] - before[voluntary] >= 20
    assert after[involuntary] >= before[involuntary] >= 0


def test_a_started_apiserver_has_the_probe_and_the_request_parts():
    """No flag, argument or benchmark selects them: any process that
    serves the API observes its own interpreter and splits its requests."""
    import urllib.request

    from kubernetes_tpu.apiserver.http import APIServer
    from kubernetes_tpu.apiserver.master import Master

    assert probes() == []
    srv = APIServer(Master()).start()
    try:
        assert len(probes()) == 1
        with urllib.request.urlopen(
                srv.base_url + "/api/v1/namespaces/default/pods") as resp:
            assert resp.status == 200
        # the handler folds its parts after the response's last byte
        deadline = time.monotonic() + 5.0
        text = ""
        while "apiserver_request_offcpu_seconds_total{" not in text \
                and time.monotonic() < deadline:
            time.sleep(0.01)
            text = srv.metrics_registry.render_text()
        assert 'apiserver_request_part_seconds_total{verb="get",' \
            'resource="pods",group="http",part="send"}' in text
        assert 'apiserver_request_offcpu_seconds_total{verb="get",' \
            'resource="pods"}' in text
    finally:
        srv.stop()
