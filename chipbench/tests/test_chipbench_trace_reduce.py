"""The trace reduction, on a small trace recorded on a TPU v5e
(``tools/record_test_trace.py``: three calls of a 512x512 matmul+sin,
each followed by a 5 ms host sleep in a ``test.host_wait`` span) and on
hand-made events.

Events of ``data/small.xplane.pb`` (ns, device clock; XLA Ops line):

    call 1: copy-start 47190452-47190465, copy-done 47190465-47190468,
            convolution_sine_fusion 47190470-47198295
    call 2: copy-start 54048270-54048283, copy-done 54048284-54048287,
            convolution_sine_fusion 54048289-54056114
    call 3: copy-start 60565685-60565698, copy-done 60565699-60565701,
            convolution_sine_fusion 60565703-60573526
    XLA Modules starts: 47190448, 54048268, 60565682
    host DoEnqueueProgram ends: 48668151, 55511561, 62030172
    host chipbench.window: 48322569-68361248

Clock shift: max(48668151-47190448, 55511561-54048268,
62030172-60565682) = max(1477703, 1463293, 1464490) = 1477703.
Busy per call (touching ops merge): 16+7825, 13+3+7825, 13+2+7823 =
7841 + 7841 + 7838 = 23520 ns. Window 68361248-48322569 = 20038679 ns.
Gaps after the shift: call 1 ends 48675998, call 2 starts 55525973:
6849975; call 2 ends 55533817, call 3 starts 62043388: 6509571; call 3
ends 62051229, window ends 68361248: 6310019; window starts 48322569,
call 1 starts 48668155: 345586. The three long gaps lie in the host
sleeps.
"""
import os

import pytest

from chipbench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


@pytest.fixture(scope="module")
def small():
    return tr.reduce_file(DATA)


def test_recorded_trace_busy_and_window(small):
    assert small["devices"] == 1
    assert small["clock_shift_s"] == pytest.approx(1477703e-9, abs=1e-12)
    assert small["window_s"] == pytest.approx(20038679e-9, abs=1e-12)
    assert small["busy_s"] == pytest.approx(23520e-9, abs=1e-12)
    assert small["collective_s"] == 0.0 and small["exposed_comm_s"] == 0.0


def test_recorded_trace_ops_and_gaps(small):
    assert small["top_ops"][0][0] == "convolution_sine_fusion"
    assert small["top_ops"][0][1] == pytest.approx(
        (7825 + 7825 + 7823) * 1e-9, abs=1e-12)
    gaps = small["idle_gaps"]
    assert [round(g[1] * 1e9) for g in gaps[:4]] == [6849975, 6509571,
                                                     6310019, 345586]
    assert [g[0] for g in gaps[:3]] == ["test.host_wait"] * 3


def test_union_and_subtract():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.measure([(0, 2), (1, 3)]) == 3
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]


def test_exposed_collective_time():
    # device A: an async permute starts at 8 and is done at 14; compute
    # runs 0-8, 9-10 and 14-20, so of its interval 8-14 the parts 8-9 (the
    # start op itself) and 10-14 are exposed: 5 of 6. Device B: a
    # synchronous permute 2-6 is exposed whole: 4 of 4.
    ops = {
        "/device:TPU:0": [("fusion.1", 0, 8),
                          ("collective-permute-start.1", 8, 9),
                          ("fusion.3", 9, 10),
                          ("collective-permute-done.1", 13, 14),
                          ("fusion.2", 14, 20)],
        "/device:TPU:1": [("fusion.1", 0, 2), ("collective-permute.3", 2, 6),
                          ("fusion.2", 6, 20)],
    }
    out = tr.reduce_events(ops, [], (0, 20))
    assert out["collective_s"] == pytest.approx(5e-9)
    assert out["exposed_comm_s"] == pytest.approx(4.5e-9)
    # busy: A 0-10 and 13-20 (17), B 0-20 (20): mean 18.5
    assert out["busy_s"] == pytest.approx(18.5e-9)


def test_clock_shift_pairs_modules_with_enqueues():
    assert tr.clock_shift([10, 20], [15, 24]) == 5
    # four devices: four enqueues per launch, the last of each counts
    assert tr.clock_shift([10, 20], [1, 2, 3, 12, 14, 15, 16, 21]) == 2
    assert tr.clock_shift([10, 20, 30], [15, 24]) == 0.0


def test_op_name():
    assert tr.op_name("%fusion.4 = f32[8]{0} fusion(f32[8]{0} %x)") == \
        "fusion.4"


def test_nested_ops_count_self_time_only():
    # a loop op 0-10 holding body ops 1-3 and 4-8: the loop's self time
    # is 10 - 2 - 4 = 4; a collective under the loop but under no body op
    # (8.5-9.5) is exposed, since only innermost ops count as compute
    ops = {"/device:TPU:0": [("while.1", 0, 10), ("fusion.1", 1, 3),
                             ("fusion.2", 4, 8),
                             ("all-reduce.1", 8.5, 9.5)]}
    out = tr.reduce_events(ops, [], (0, 10))
    assert dict(out["top_ops"]) == pytest.approx(
        {"while.1": 3e-9, "fusion.1": 2e-9, "fusion.2": 4e-9,
         "all-reduce.1": 1e-9})
    assert out["busy_s"] == pytest.approx(10e-9)
    assert out["exposed_comm_s"] == pytest.approx(1e-9)
