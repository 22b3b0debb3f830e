"""The reduction from a trace to busy time, top operations and idle gaps:
hand-made interval cases, then a small recorded v5e trace."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks import trace_reduce as tr  # noqa: E402

RECORDED = os.path.join(HERE, "train_v5e.xplane.pb")


def planes(ops, window=None, host=()):
    """One chip whose ``XLA Ops`` line holds ``ops``; a host line with the
    window's annotation and ``host`` events."""
    events = list(host)
    if window:
        events.append((tr.WINDOW_NAME, *window))
    return {"/device:TPU:0": {tr.OP_LINE: list(ops), "XLA Modules": [("jit_f", 0, 99)]},
            "/host:CPU": {"main": events}}


@pytest.mark.parametrize("intervals,merged,busy", [
    ([(0, 1), (2, 3)], [(0, 1), (2, 3)], 2),                 # apart
    ([(0, 2), (1, 3)], [(0, 3)], 3),                         # overlap
    ([(0, 10), (2, 3), (4, 5)], [(0, 10)], 10),              # nested
    ([(0, 1), (1, 2)], [(0, 2)], 2),                         # touching
    ([(5, 6), (0, 1)], [(0, 1), (5, 6)], 2),                 # out of order
    ([(3, 3), (4, 2)], [], 0),                               # empty and inverted
    ([], [], 0),
])
def test_union(intervals, merged, busy):
    assert tr.union(intervals) == merged
    assert tr.total(tr.union(intervals)) == busy


def test_gaps_are_the_complement_in_the_window():
    assert tr.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert tr.gaps([(0, 6)], 0, 6) == []
    assert tr.gaps([], 2, 3) == [(2, 3)]


def test_busy_is_clipped_to_the_window_and_not_counted_twice():
    ops = [("a", 0.0, 2.0), ("b", 1.0, 3.0), ("c", 1.5, 1.6), ("d", 9.0, 12.0)]
    out = tr.reduce_planes(planes(ops, window=(1.0, 10.0)))
    # union in the window: [1, 3] and [9, 10]
    assert out["busy_s"] == pytest.approx(3.0)
    assert out["window_s"] == pytest.approx(9.0)
    assert out["devices"] == 1
    table = dict(map(tuple, out["device_ops"]))
    assert table == pytest.approx({"a": 1.0, "b": 2.0, "c": 0.1, "d": 1.0})
    assert [name for name, _ in out["device_ops"]] == ["b", "a", "d", "c"]


def test_gaps_are_named_by_the_span_that_covers_most_of_them():
    ops = [("a", 0.0, 1.0), ("a", 4.0, 5.0), ("a", 5.5, 6.0)]
    host = [("PjitFunction(f)", 5.0, 5.5)]
    spans = [("bench.sync", 0.9, 3.0), ("bench.dispatch", 3.0, 4.2)]  # from the window's start
    out = tr.reduce_planes(planes(ops, window=(0.0, 6.5), host=host), host_spans=spans)
    assert out["idle_gaps"][0] == ["bench.sync", pytest.approx(3.0)]
    assert out["idle_gaps"][1] == ["host:PjitFunction(f)", pytest.approx(0.5)]
    assert out["idle_gaps"][2] == [tr.UNATTRIBUTED, pytest.approx(0.5)]


def test_no_window_annotation_falls_back_to_the_extent_of_the_ops():
    out = tr.reduce_planes(planes([("a", 2.0, 3.0), ("b", 5.0, 6.0)]))
    assert out["window_s"] == pytest.approx(4.0)
    assert out["busy_s"] == pytest.approx(2.0)


def test_a_trace_with_no_device_operation_reads_zero():
    out = tr.reduce_planes({"/host:CPU": {"main": [(tr.WINDOW_NAME, 0.0, 1.0)]}})
    assert out["busy_s"] == 0.0 and out["device_ops"] == [] and out["idle_gaps"] == []


def test_two_chips_are_averaged():
    both = planes([("a", 0.0, 1.0)], window=(0.0, 2.0))
    both["/device:TPU:1"] = {tr.OP_LINE: [("a", 0.0, 2.0)]}
    out = tr.reduce_planes(both)
    assert out["devices"] == 2
    assert out["busy_s"] == pytest.approx(1.5)
    assert out["device_ops"] == [["a", pytest.approx(1.5)]]


def test_short_name_keeps_the_instruction_and_a_custom_calls_target():
    hlo = ('%iteration.7 = (f32[8,16]{1,0}) custom-call(s32[8,152]{1,0} %copy.1), '
           'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
    assert tr.short_name(hlo) == "iteration.7 tpu_custom_call"
    assert tr.short_name("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop") == "fusion.3"
    assert tr.short_name("PjitFunction(iteration)") == "PjitFunction(iteration)"


def test_recorded_v5e_trace():
    """The start of the first traced chip run of the ALS train cell (PR 23,
    TPU v5 lite, jax 0.9.0): the device and host planes, cut to the events
    that start in the first 0.12 s so that the file stays small. In it the
    window's annotation lasts 5.07 s, the first bucket's fused half-step
    (``iteration.5``) runs whole and the second (``iteration.6``) is the last
    operation kept."""
    planes_ = tr.read_planes(RECORDED)
    assert set(planes_) == {"/device:TPU:0", "/host:CPU"}
    assert len(planes_["/device:TPU:0"][tr.OP_LINE]) == 969
    assert tr.find_window(planes_) == pytest.approx((0.0420, 5.1085), abs=1e-3)
    out = tr.reduce_planes(planes_)
    assert out["devices"] == 1
    assert out["window_s"] == pytest.approx(5.066565048)
    assert out["busy_s"] == pytest.approx(0.198540337)
    assert out["device_ops"][0] == ["iteration.6 tpu_custom_call", pytest.approx(0.17310877)]
    assert out["device_ops"][1] == ["iteration.5 tpu_custom_call", pytest.approx(0.022057241)]
    assert len(out["device_ops"]) == 10
    # one long gap after the last operation kept; the host was in the
    # driver's sync (the profiler's Python tracer names the frame)
    who, seconds = out["idle_gaps"][0]
    assert seconds == pytest.approx(4.868023225)
    assert who.startswith("host:") and who.endswith("sync")
    assert len(out["idle_gaps"]) == 5


def test_find_xplane_takes_the_newest_under_a_trace_directory(tmp_path):
    for stamp in ("2026_01_01", "2026_09_27"):
        d = tmp_path / "plugins" / "profile" / stamp
        d.mkdir(parents=True)
        (d / "host.xplane.pb").write_bytes(b"")
    assert tr.find_xplane(str(tmp_path)).endswith("2026_09_27/host.xplane.pb")
    with pytest.raises(FileNotFoundError):
        tr.find_xplane(str(tmp_path / "nothing"))
