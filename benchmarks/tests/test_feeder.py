"""The load generator's schedule and arithmetic. Importing it must not
bring JAX in: that is what keeps the child off the chip."""

import math
import subprocess
import sys

import pytest

from benchmarks import feeder


def test_the_feeder_imports_no_jax_and_no_numpy():
    code = ("import sys; import benchmarks.feeder; "
            "print('jax' in sys.modules, 'numpy' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=feeder.os.path.dirname(
                             feeder.os.path.dirname(feeder.__file__)))
    assert out.stdout.split() == ["False", "False"], out.stderr


def test_uniform_schedule():
    assert feeder.open_loop_schedule(100.0, 4, "uniform", 7) == \
        [0.0, 0.01, 0.02, 0.03]


def test_every_seed_offers_the_same_gaps_in_another_order():
    a = feeder.open_loop_schedule(100.0, 3000, "exponential", 1, block=1000)
    b = feeder.open_loop_schedule(100.0, 3000, "exponential", 2, block=1000)
    gaps = lambda s: sorted(round(y - x, 12) for x, y in zip([0.0] + s, s))
    assert a != b and gaps(a[:1000]) == gaps(b[:1000])
    assert a[999] == pytest.approx(b[999])            # a block takes the same
    assert a[2999] / 3000 == pytest.approx(0.01, rel=0.01)   # mean gap 1/rate
    with pytest.raises(ValueError):
        feeder.open_loop_schedule(0, 1, "uniform", 1)


def test_percentile_interpolates_and_carries_infinity():
    assert feeder.percentile([1, 2, 3, 4, 5], 0.5) == 3
    assert feeder.percentile([0, 10], 0.99) == pytest.approx(9.9)
    assert feeder.percentile([1.0] * 99 + [math.inf], 0.5) == 1.0
    assert feeder.percentile([1.0] * 50 + [math.inf] * 50, 0.99) == math.inf


def _pods(n, stall_from=None, stall_s=0.0):
    """n pods due every 10 ms from t=100, each bound 50 ms after it was
    sent; from pod ``stall_from`` on, the server stalls ``stall_s``."""
    pods, order = {}, []
    for i in range(n):
        due = 100.0 + i * 0.01
        stalled = stall_from is not None and i >= stall_from
        bound = due + 0.05 + (stall_s if stalled else 0.0)
        pods[f"p{i}"] = {"phase": "window", "due_t": due, "sent_t": due,
                         "created_t": due + 0.001, "bound_t": bound,
                         "host": "n"}
        order.append(f"p{i}")
    return pods, order


def test_a_stall_in_the_window_moves_the_tail_and_the_rate():
    calm = feeder.summarize(*_pods(1000), 100.0, 110.0, "open")
    assert calm["attempted"] == 1000 and calm["failed"] == 0
    assert calm["pods_per_s"] == pytest.approx(99.5, abs=0.2)
    assert calm["bound_p99_s"] == pytest.approx(0.05)
    # the last 30 pods wait 2 s: they are bound after the close
    stalled = feeder.summarize(*_pods(1000, 970, 2.0), 100.0, 110.0, "open")
    assert stalled["bound_p50_s"] == pytest.approx(0.05)
    assert stalled["bound_p99_s"] == pytest.approx(2.05)
    # 30 of 1,000 stalled: the 90th percentile does not see them
    assert stalled["bound_p90_s"] == pytest.approx(0.05)
    assert stalled["pods_per_s"] == pytest.approx(97.0, abs=0.2)


def test_an_unbound_pod_is_failed_and_beyond_any_limit():
    pods, order = _pods(100)
    del pods["p99"]["bound_t"]
    pods["p98"] = {"phase": "window", "due_t": 100.98, "sent_t": 100.98,
                   "error": "boom"}
    s = feeder.summarize(pods, order, 100.0, 110.0, "open")
    assert s["failed"] == 2 and s["bound_p99_s"] == math.inf
    assert s["bound_p50_s"] == pytest.approx(0.05)


def test_the_open_loop_times_a_create_from_when_it_was_due():
    pods, order = _pods(10)
    for r in pods.values():
        r["sent_t"] += 0.5                      # the generator ran late
        r["bound_t"] += 0.5
    s = feeder.summarize(pods, order, 100.0, 110.0, "open")
    assert s["bound_p50_s"] == pytest.approx(0.55)
    assert s["late_p99_s"] == pytest.approx(0.5)
    closed = feeder.summarize(pods, order, 100.0, 110.0, "closed")
    assert closed["bound_p50_s"] == pytest.approx(0.05)
