"""solve_work on one hand-computed shape, and the table of peaks."""

import pytest

from benchmarks import roofline


def test_solve_work_on_a_hand_computed_wave():
    # P = 4 pods, N = 10 nodes, R = 2
    # node planes: 10 * (3*2*4 + 2 + 2 + 4 + 8) = 10 * 40 = 400
    # pod rows:     4 * (2*4 + 24)              =  4 * 32 = 128
    # static mask:  4 * 10 = 40; outputs: 4 * 8 = 32      => 600 bytes
    # ops: 4 * 10 * (7*2 + 7) = 840
    assert roofline.solve_work({"P": 4, "N": 10, "R": 2}) == (840, 600)


def test_the_served_bucket_is_bytes_bound_on_a_v5e():
    peaks = roofline.peaks_for("TPU v5 lite")
    seconds, bound = roofline.least_seconds(
        {"P": 1024, "N": 5000, "R": 2}, peaks)
    assert bound == "bytes"
    assert seconds == pytest.approx(5_360_960 / 819e9)


def test_a_device_that_is_not_in_the_table_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks_for("cpu")
