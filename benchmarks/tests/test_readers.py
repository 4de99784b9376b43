"""histogram_mean and counter_ratio on two render() texts."""

import pytest

from benchmarks.readers import counter_ratio, histogram_mean, promtext

BEFORE = """# HELP scheduler_wave_solve_seconds Solver time per wave
# TYPE scheduler_wave_solve_seconds histogram
scheduler_wave_solve_seconds_bucket{le="0.01"} 3
scheduler_wave_solve_seconds_bucket{le="+Inf"} 4
scheduler_wave_solve_seconds_sum 0.5
scheduler_wave_solve_seconds_count 4
apiserver_request_latencies_seconds_sum{verb="post",resource="pods"} 1.0
apiserver_request_latencies_seconds_count{verb="post",resource="pods"} 100
apiserver_request_latencies_seconds_sum{verb="get",resource="pods"} 9.0
apiserver_request_latencies_seconds_count{verb="get",resource="pods"} 3
solver_wave_program_total{program="pallas",platform="tpu"} 10
scheduler_wave_pods_total 40
"""
AFTER = """scheduler_wave_solve_seconds_sum 0.74
scheduler_wave_solve_seconds_count 16
apiserver_request_latencies_seconds_sum{verb="post",resource="pods"} 4.0
apiserver_request_latencies_seconds_count{verb="post",resource="pods"} 1100
apiserver_request_latencies_seconds_sum{verb="get",resource="pods"} 99.0
apiserver_request_latencies_seconds_count{verb="get",resource="pods"} 5
solver_wave_program_total{program="pallas",platform="tpu"} 19
solver_wave_program_total{program="scan",platform="cpu"} 3
scheduler_wave_pods_total 1240
"""
CTX = {"metrics_before": BEFORE, "metrics_after": AFTER}


def test_parse_reads_labels_and_numbers():
    rows = promtext.parse(BEFORE)
    assert ("scheduler_wave_solve_seconds_bucket", {"le": "+Inf"}, 4.0) in rows
    assert promtext.total(BEFORE, "apiserver_request_latencies_seconds_sum",
                          {}) == 10.0


def test_histogram_mean_is_delta_sum_over_delta_count():
    args = {"series": "scheduler_wave_solve_seconds", "scale": 1000}
    assert histogram_mean.read(CTX, args) == pytest.approx(20.0)   # .24/12
    posts = {"series": "apiserver_request_latencies_seconds",
             "labels": {"verb": "post", "resource": "pods"}, "scale": 1000}
    assert histogram_mean.read(CTX, posts) == pytest.approx(3.0)   # 3/1000


def test_histogram_mean_with_nothing_observed_returns_nothing():
    same = {"metrics_before": BEFORE, "metrics_after": BEFORE}
    assert histogram_mean.read(
        same, {"series": "scheduler_wave_solve_seconds"}) is None
    assert histogram_mean.read(CTX, {"series": "no_such_series"}) is None


def test_counter_ratio():
    pods = {"numerator": {"series": "scheduler_wave_pods_total"},
            "denominator": {"series": "scheduler_wave_solve_seconds_count"}}
    assert counter_ratio.read(CTX, pods) == pytest.approx(100.0)  # 1200/12
    share = {"numerator": {"series": "solver_wave_program_total",
                           "labels": {"program": "pallas",
                                      "platform": "tpu"}},
             "denominator": {"series": "solver_wave_program_total"},
             "scale": 100}
    assert counter_ratio.read(CTX, share) == pytest.approx(75.0)   # 9/12
