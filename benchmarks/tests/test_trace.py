"""The reduction from trace to numbers, on a small recorded trace: a device
plane with overlapping operations, a second line that must not be counted,
and a host plane that is no device."""

import pytest

from benchmarks.harness import trace as tr
from benchmarks.readers import roofline as roofline_reader
from benchmarks.readers import trace_event_ms, trace_idle

MS = 1_000_000

TRACE = {"planes": [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            ["fusion.1", 0 * MS, 2 * MS],
            ["solve_kernel.3", 1 * MS, 3 * MS],      # overlaps fusion.1
            ["copy.2", 10 * MS, 1 * MS],
            ["solve_kernel.3", 20 * MS, 5 * MS],
            ["fusion.9", 22 * MS, 1 * MS]]},         # inside the kernel
        {"name": "XLA Modules", "events": [
            ["jit_everything", 0, 40 * MS]]}]},
    {"name": "/host:CPU", "lines": [
        {"name": "XLA Ops", "events": [["python", 0, 100 * MS]]}]}]}


def test_busy_is_the_union_of_overlapping_events():
    plane = tr.device_planes(TRACE)[0]
    # [0,4) + [10,11) + [20,25) = 10 ms; the sum of durations would be 12
    assert tr.busy_union_ns(tr.line_events(plane, "XLA Ops")) == 10 * MS
    assert tr.busy_s(TRACE) == pytest.approx(0.010)


def test_only_device_planes_count():
    assert [p["name"] for p in tr.device_planes(TRACE)] == ["/device:TPU:0"]
    assert tr.busy_s({"planes": TRACE["planes"][1:]}) is None


def test_idle_share_reader():
    ctx = {"trace": TRACE, "traced_s": 0.040}
    assert trace_idle.read(ctx, {}) == pytest.approx(75.0)
    assert trace_idle.read({"trace": None}, {}) is None
    host_only = {"trace": {"planes": TRACE["planes"][1:]}, "traced_s": 1.0}
    assert trace_idle.read(host_only, {}) is None


def test_kernel_time_is_the_mean_of_its_events():
    ctx = {"trace": TRACE}
    args = {"line": "XLA Ops", "pattern": "solve_kernel"}
    assert trace_event_ms.read(ctx, args) == pytest.approx(4.0)  # (3+5)/2
    assert trace_event_ms.read(ctx, {"pattern": "no_such_op"}) is None


def test_top_ops_and_gaps():
    assert tr.top_ops(TRACE, n=2) == [["solve_kernel.3", 0.008],
                                      ["fusion.1", 0.002]]


def test_idle_gaps_go_to_what_the_host_was_doing():
    # the device idles in [4,10) and [11,20) ms; a full collection holds
    # every thread in [5,7), the loop encodes in [4,9) (of which [5,7) is
    # the collection's), solves in [9,10.5) and commits in [12,30)
    spans = [["encode", 4 * MS, 5 * MS], ["gc", 5 * MS, 2 * MS],
             ["solve", 9 * MS, 3 * MS // 2], ["commit", 12 * MS, 18 * MS]]
    gaps = dict(tr.idle_gaps(dict(TRACE, host_spans=spans)))
    assert gaps == {"commit": pytest.approx(0.008),
                    "encode": pytest.approx(0.003),
                    "gc": pytest.approx(0.002),
                    "solve": pytest.approx(0.001),
                    "no span": pytest.approx(0.001)}        # [11,12)
    assert sum(gaps.values()) == pytest.approx(0.015)
    # the device is busy in [0,4) [10,11) [20,25), 10 ms: [20,25) lies in
    # the commit's span, [10,10.5) in the solve's
    assert tr.busy_inside(dict(TRACE, host_spans=spans), "commit") == \
        pytest.approx(0.5)
    assert tr.busy_inside(dict(TRACE, host_spans=spans), "solve") == \
        pytest.approx(0.05)
    assert tr.busy_inside(TRACE, "solve") is None
    # with no host span in the trace every idle second is unexplained
    assert tr.idle_gaps(TRACE) == [["no span", pytest.approx(0.015)]]


def test_an_hlo_line_is_cut_to_a_name_one_can_read():
    line = ("%_solve_pallas_x32.1 = (s32[8,128]{1,0:T(8,128)}, s32[8,128]"
            "{1,0:T(8,128)}) custom-call(s32[1024,40,128]{2,1,0:T(8,128)S(1)}"
            " %copy.32, s32[1024,1,128]{2,1,0} %dus.21), custom_call_target="
            "\"tpu_custom_call\"")
    assert tr.short_name(line) == \
        "%_solve_pallas_x32.1 custom-call s32[1024,40,128]"
    assert tr.short_name(line, shape=False) == \
        "%_solve_pallas_x32.1 custom-call"
    assert tr.short_name("%reshape.16 = u8[20272,2]{1,0} reshape(u8[40544]"
                         "{0} %buf.1)") == "%reshape.16 reshape u8[40544]"
    assert tr.short_name("fusion.1") == "fusion.1"


def test_roofline_reader_returns_nothing_rather_than_zero():
    ctx = {"trace": TRACE, "traced_waves": [], "device_kind": "TPU v5 lite"}
    args = {"pattern": "solve_kernel"}
    assert roofline_reader.read(ctx, args) is None          # no wave
    ctx["traced_waves"] = [{"dims": {"P": 1024, "N": 5000, "R": 2}}] * 2
    assert roofline_reader.read(ctx, {"pattern": "absent"}) is None
    share = roofline_reader.read(ctx, args)
    # bytes-bound: 5,360,960 B / 819e9 B/s = 6.5457 us a wave, two launches
    # over 8 ms of kernel time
    assert share == pytest.approx(100 * 2 * 5_360_960 / 819e9 / 0.008)
    assert ctx["notes"]["roofline_bound"] == "bytes"
    ctx["device_kind"] = "TPU v9 imaginary"
    with pytest.raises(KeyError, match="no peaks"):
        roofline_reader.read(ctx, args)
