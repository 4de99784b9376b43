"""What it costs a thread to get the interpreter back.

One interpreter lock serves every thread of a control-plane process, and
nothing in CPython says who holds it or for how long. What can be had from
inside is the other side: how long a thread that has become runnable waits
until it runs. ``ensure()`` starts one daemon thread that sleeps a fixed
``PERIOD_S`` and observes how much later than that it was running again —
``process_interpreter_handoff_seconds``. Every blocking call on a pod's path
(a ``recv``, a ``sendall``, a ``Condition.wait``, a ``device_put``) pays
that wait when it returns; the off-CPU shares only show it summed with the
blocking itself. An idle process reads the timer's own slack (about
0.1 ms); beside threads that never let go it reads the switch interval and
more (on the benchmark's host the slack alone is 0.9 ms: its timers tick
in 10 ms). The probe cannot name the holder.

``process_context_switches_total{kind}`` is the kernel's count of the
times a thread of this process left a core, by its own doing (``voluntary``:
it blocked, or handed the interpreter on) or not (``involuntary``), read
from ``getrusage`` when a registry renders. It counts every thread, the
runtime's own too. Over a window and divided by pods it is the number of
thread hops a pod costs.
"""

from __future__ import annotations

import resource
import threading
import time

from kubernetes_tpu.util import metrics, tracing

__all__ = ["ensure", "reset", "PERIOD_S"]

PERIOD_S = 0.010                # 100 samples a second

_reg = metrics.default_registry()
_HANDOFF = _reg.histogram(
    "process_interpreter_handoff_seconds",
    "Seconds a thread that slept a fixed period was late to run again: the "
    "timer's slack and the wait for the interpreter lock",
    buckets=(0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
             0.05))
_SWITCHES = _reg.counter(
    "process_context_switches_total",
    "Times a thread of this process left a core, by kind (getrusage)",
    ("kind",))

_lock = threading.Lock()         # the probe's start and stop; renders
_probe = None                   # (thread, its stop flag) once ensure() ran


def _run(stop: threading.Event) -> None:
    # A role of its own, read by nobody: where the CPU clock is sampled
    # on a 10 ms tick (the benchmark's host), a thread that wakes on that
    # tick is charged whole ticks it never ran — 13 % of a core for these
    # 20 us a wake-up — and unmarked they would land in ``other``.
    tracing.role("interp_probe")
    try:
        while not stop.is_set():
            due = time.monotonic() + PERIOD_S
            time.sleep(PERIOD_S)
            _HANDOFF.observe(max(0.0, time.monotonic() - due))
    finally:
        tracing.role_end()


def ensure() -> None:
    """Start this process's probe; a second call does nothing."""
    global _probe
    with _lock:
        if _probe is None:
            stop = threading.Event()
            thread = threading.Thread(target=_run, args=(stop,), daemon=True,
                                      name="interp-probe")
            _probe = (thread, stop)
            thread.start()


def reset() -> None:
    """Tests only: stop the probe, so that a count of threads holds."""
    global _probe
    with _lock:
        probe, _probe = _probe, None
    if probe is not None:
        probe[1].set()
        probe[0].join(timeout=5.0)


def _collect() -> None:
    """Render-time collector: bring the two counts up to the kernel's."""
    usage = resource.getrusage(resource.RUSAGE_SELF)
    with _lock:
        for kind, count in (("voluntary", usage.ru_nvcsw),
                            ("involuntary", usage.ru_nivcsw)):
            _SWITCHES.inc(kind, by=max(0, count - _SWITCHES.value(kind)))


_reg.add_collector(_collect)
