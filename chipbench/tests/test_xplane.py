"""The trace reduction: busy-interval union, idle gaps and their labels,
program device time; on hand-made events and on a small trace recorded
on a TPU v5e (``data/v5e_refresh.xplane.pb``)."""
from pathlib import Path

import pytest

from chipbench import xplane
from chipbench.record import FULL_FORWARD, SERVE_FORWARD

RECORDED = Path(__file__).parent / "data" / "v5e_refresh.xplane.pb"
E = xplane.Event


def test_union_and_gaps():
    iv = [(5, 9), (0, 3), (2, 4), (8, 12), (20, 25)]
    assert xplane.union(iv, 0, 30) == [(0, 4), (5, 12), (20, 25)]
    assert xplane.union(iv, 1, 22) == [(1, 4), (5, 12), (20, 22)]
    busy = xplane.union(iv, 0, 30)
    assert xplane.gaps(busy, 0, 30) == [(4, 5), (12, 20), (25, 30)]
    assert xplane.gaps([], 0, 10) == [(0, 10)]


def test_busy_seconds_averages_devices():
    tr = xplane.Trace(
        ops={"/device:TPU:0": [E("a", 0, 10 ** 9), E("b", 5 * 10 ** 8,
                                                     15 * 10 ** 8)],
             "/device:TPU:1": [E("a", 0, 5 * 10 ** 8)]},
        modules={}, spans=[])
    assert xplane.busy_seconds(tr, 0, 2 * 10 ** 9) == pytest.approx(1.0)
    assert xplane.busy_seconds(xplane.Trace({}, {}, []), 0, 1) is None
    # a program's span counts as busy, gaps between its operations too
    tr = xplane.Trace(ops={"/device:TPU:0": [E("a", 0, 4), E("b", 6, 10)]},
                      modules={"/device:TPU:0": [E("jit_f(1)", 0, 10)]},
                      spans=[])
    assert xplane.busy_seconds(tr, 0, 20) == pytest.approx(10e-9)


def test_label_gaps_by_host_span():
    spans = [E("bench.window", 0, 100), E("bench.tick", 10, 40),
             E("bench.submit", 50, 55), E("bench.tick", 60, 70)]
    idle = [(0, 20), (45, 65)]
    got = xplane.label_gaps(idle, spans)
    assert got["tick"] == pytest.approx(15e-9)       # 10-20 and 60-65
    assert got["submit"] == pytest.approx(5e-9)
    assert got["loop"] == pytest.approx(20e-9)       # 0-10, 45-50, 55-60


def test_program_times_and_window():
    tr = xplane.Trace(ops={}, modules={"/device:TPU:0": [
        E("jit_full(12)", 10, 30), E("jit_full_frozen(3)", 40, 45),
        E("jit__serve(7)", 50, 60), E("jit_fullx(1)", 61, 62),
        E("jit_full(12)", 95, 120)]},
        spans=[E("bench.window", 5, 100)])
    lo, hi = xplane.window(tr)
    assert (lo, hi) == (5, 100)
    full = xplane.program_times(tr, FULL_FORWARD, lo, hi)
    assert full == pytest.approx([20e-9, 5e-9])
    assert xplane.program_times(tr, SERVE_FORWARD, lo, hi) == \
        pytest.approx([10e-9])


def test_recorded_v5e_trace():
    """One feature update and refresh tick on the full-cache path and one
    subgraph tick, gcn on Flickr, traced on one v5e: its window is the
    extent of the benchmark spans in it."""
    tr = xplane.load(RECORDED)
    assert list(tr.ops) == ["/device:TPU:0"]
    assert sorted(s.name for s in tr.spans) == [
        "bench.tick", "bench.tick", "bench.update"]
    lo = min(s.start_ns for s in tr.spans)
    hi = max(s.end_ns for s in tr.spans)
    busy = xplane.busy_seconds(tr, lo, hi)
    merged = xplane.busy_intervals(tr, "/device:TPU:0", lo, hi)
    idle = xplane.gaps(merged, lo, hi)
    # busy and idle partition the window
    assert busy + sum(e - s for s, e in idle) * 1e-9 == \
        pytest.approx((hi - lo) * 1e-9)
    labelled = xplane.label_gaps(idle, tr.spans)
    assert sum(labelled.values()) == pytest.approx(
        sum(e - s for s, e in idle) * 1e-9)
    # the host spent the idle time inside the two ticks
    assert labelled["tick"] > 0.9 * sum(labelled.values())
    full = xplane.program_times(tr, FULL_FORWARD, lo, hi)
    serve = xplane.program_times(tr, SERVE_FORWARD, lo, hi)
    assert full == pytest.approx([0.030198181], rel=1e-6)
    assert serve == pytest.approx([0.042665872], rel=1e-6)
    # the programs ran inside the device's busy time
    assert full[0] + serve[0] <= busy < (hi - lo) * 1e-9
    top = xplane.top_ops(tr, lo, hi)
    assert len(top) == 10
    assert top == sorted(top, key=lambda t: -t[1])
    assert all(name.startswith("%") and " = " in name for name, _ in top)


def test_op_name():
    assert xplane.op_name(
        "%fusion.3 = f32[22313,4,7]{2,1,0:T(4,128)S(1)} fusion(f32[1]"
    ) == "%fusion.3 = f32[22313,4,7]"
    assert xplane.op_name("%copy.66 = u32[131072,2,32]{0,2,1} copy(x)") \
        == "%copy.66 = u32[131072,2,32]"
    assert xplane.op_name("plain") == "plain"
