"""The reduction from a profiler trace to busy time, op shares and idle gaps,
on small recorded traces and on a hand-made one."""

import glob
import os

import numpy as np
import pytest

from bench import tracereduce as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = sorted(glob.glob(os.path.join(DATA, "*.xplane.pb")))


def _ops(*events):
    return T._ops([(n, c, s, d) for n, c, s, d in events])


def test_union_merges_overlaps_and_keeps_gaps():
    s, e = T.union(np.array([5.0, 0.0, 2.0, 10.0]), np.array([6.0, 3.0, 4.0, 12.0]))
    assert s.tolist() == [0.0, 5.0, 10.0] and e.tolist() == [4.0, 6.0, 12.0]


def test_hand_made_trace():
    """Two devices over a 100 ns window: busy time is the union of each
    device's ops, shares are of busy time, gaps are labelled by host span."""
    trace = T.Trace(
        devices={
            "/device:TPU:0": _ops(("fusion.1", "", 0, 40), ("scatter.2", "", 20, 30),
                                  ("all-to-all.3", "", 80, 20)),
            "/device:TPU:1": _ops(("fusion.1", "", 0, 50), ("all-to-all.3", "", 50, 10)),
        },
        spans=[("step", 0.0, 50.0), ("scatter_back", 50.0, 60.0)],
    )
    s = T.summarize(trace, t0=0.0, t1=100.0)
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx((70 + 60) / 2 * 1e-9)
    assert s.idle_share == pytest.approx(1 - 65 / 100)
    assert s.share(("scatter",)) == pytest.approx((30 / 70 + 0) / 2)
    assert s.share(("all-to-all",)) == pytest.approx((20 / 70 + 10 / 60) / 2)
    assert s.share(("gather",)) is None  # no op to read: no share, not 0
    assert s.top_ops[0] == ["fusion.1", pytest.approx(45e-9)]
    # device 0 idles over [50, 80): 10 ns in scatter_back, 20 outside calls
    assert s.idle_gaps == [["outside calls", pytest.approx(30e-9)]]


HLO = """HloModule jit_step, entry_computation_layout={(f32[8])->f32[8]}

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %sum = f32[] add(%a, %b)
}

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0:T(1024)S(1)} parameter(0)
  ROOT %scatter.1 = f32[8]{0:T(1024)} scatter(%p, %p, %p), to_apply=%add, metadata={op_name="jit(step)/scatter-add"}
}

%body (q: f32[8]) -> f32[8] {
  %q = f32[8] parameter(0)
  ROOT %fusion.7 = f32[8]{0:T(1024)} fusion(%q), kind=kCustom, calls=%fused_computation, metadata={op_name="jit(step)/while/body/scatter-add"}
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0:T(1024)} parameter(0)
  %fusion.2 = f32[8]{0:T(1024)} fusion(%x), kind=kLoop, calls=%fused_computation.9, metadata={op_name="jit(step)/mul"}
  ROOT %while.3 = (f32[8]{0:T(1024)}) while(%fusion.2), condition=%cond, body=%body
}
"""


def test_kinds_come_from_the_programs_hlo():
    """A fusion is a scatter where its fused computation holds one; the same
    instruction name in another module is not; a while loop is control flow
    and stays out of the breakdown."""
    hlo = T.index_hlo([HLO])
    assert hlo["jit_step"]["fusion.7"].opcodes == {"fusion", "parameter", "scatter"}
    assert "scatter" not in hlo["jit_step"]["fusion.2"].opcodes
    assert hlo["jit_step"]["while.3"].opcode == "while"
    trace = T.Trace(devices={"/device:TPU:0": _ops(
        ("while.3", "jit_step", 0, 100), ("fusion.7", "jit_step", 10, 30),
        ("fusion.7", "jit_other", 50, 20), ("fusion.2", "jit_step", 80, 10))}, spans=[])
    s = T.summarize(trace, hlo)
    assert s.busy_s == pytest.approx(100e-9)
    assert s.share(("scatter",)) == pytest.approx(0.3)
    assert s.share(("all-to-all",)) is None
    assert [n for n, _ in s.top_ops] == ["fusion.7 scatter-add", "fusion.7", "fusion.2 mul"]


def test_names_from_the_trace():
    assert T.op_name("%fusion.427 = s32[4,64]{1,0} fusion(%a), kind=kLoop") == "fusion.427"
    assert T.op_name("%copy-done") == "copy-done"
    assert T.module_name("jit__run_scan(8181738304984545327)") == "jit__run_scan"
    assert T._in_modules([5.0, 15.0, 25.0], [("b", 10.0, 20.0), ("a", 0.0, 8.0)]) == ["a", "b", ""]


@pytest.mark.parametrize("path", RECORDED, ids=[os.path.basename(p) for p in RECORDED])
def test_recorded_trace(path):
    """A scatter program run in three `round` spans, with its optimized HLO
    where the trace has no names of its own to go by: the summary equals a
    plain recount of the events the trace holds."""
    trace = T.read(path, ["round"])
    hlo_path = path.replace(".xplane.pb", ".hlo.txt")
    hlo = T.index_hlo([open(hlo_path).read()]) if os.path.exists(hlo_path) else None
    assert [n for n, _, _ in trace.spans] == ["round"] * 3
    assert len(trace.devices) == 1
    (ops,) = trace.devices.values()
    assert set(ops.modules) == {"jit__lambda"}
    # the device's clock may run a millisecond off the host's
    t0 = min(trace.spans[0][1], ops.start.min())
    t1 = max(trace.spans[-1][2], ops.end.max())
    s = T.summarize(trace, hlo)
    assert not s.truncated
    assert s.window_s == pytest.approx((t1 - t0) / 1e9)
    # busy: every instant of the window covered by some op, counted at 1 ns
    grid = np.zeros(int(t1 - t0) + 1, bool)
    for a, b in zip(ops.start, ops.end):
        lo, hi = max(a, t0) - t0, min(b, t1) - t0
        if hi > lo:
            grid[int(lo):int(hi)] = True
    assert s.busy_s == pytest.approx(grid.sum() / 1e9, rel=1e-3, abs=5e-9)
    assert 0 < s.busy_s < s.window_s
    assert s.idle_share == pytest.approx(1 - s.busy_s / s.window_s)
    assert 0 < s.share(("scatter",)) < 1
    assert s.share(("all-to-all",)) is None
    assert any("scatter" in n for n, _ in s.top_ops)
    # the longest gap on the device lies between two rounds, outside calls
    assert s.idle_gaps[0][0] == "outside calls"
    assert s.idle_gaps[0][1] > 1e-3


def test_a_device_record_that_stops_early_ends_the_window():
    """A profiler whose buffer filled drops the rest of the window: the
    window ends where the device's record ends, so no idle time is made up."""
    ms = 1e6
    trace = T.Trace(devices={"/device:TPU:0": _ops(("fusion.1", "", 0, 40 * ms),
                                                   ("fusion.2", "", 50 * ms, 30 * ms))},
                    spans=[("round", 0.0, 90 * ms), ("round", 100 * ms, 200 * ms)])
    s = T.summarize(trace)
    assert s.truncated
    assert s.window_s == pytest.approx(80e-3)
    assert s.idle_share == pytest.approx(10 / 80)
    assert not T.summarize(T.Trace(devices=trace.devices, spans=trace.spans[:1])).truncated
