"""The program's ``serve.*`` spans in a trace: nesting, the device-idle
part of a span, idle time put down to the innermost span; the recorded v5e
trace, which holds none of them, reads as it always has; and a traced run
of each cell at a small size on the CPU reads its host-side layers."""
from pathlib import Path

import pytest

from chipbench import spans, xplane
from chipbench.record import FULL_FORWARD, SERVE_FORWARD
from chipbench.tests.test_run import SECONDS, SEED, _small

RECORDED = Path(__file__).parent / "data" / "v5e_refresh.xplane.pb"
S = spans.Span
E = xplane.Event


def test_parents_nest_per_thread():
    got = [S("serve.tick", 0, 100, "a"), S("serve.extract", 10, 50, "a"),
           S("serve.sync", 10, 40, "a"), S("serve.launch", 60, 70, "a"),
           S("serve.extract", 20, 30, "b")]
    par = spans.parents(got)
    assert [None if p is None else got[p].name for p in par] == [
        None, "serve.tick", "serve.extract", "serve.tick", None]


def test_idle_part_and_labels():
    busy = [(15, 25), (35, 60)]
    assert spans.idle_ns(S("serve.sync", 10, 40, "a"), busy) == 15
    assert spans.idle_ns(S("serve.sync", 60, 80, "a"), busy) == 20
    assert spans.mean_idle_ms(
        [S("serve.sync", 10, 40, "a"), S("serve.sync", 60, 80, "a")],
        "serve.sync", busy) == pytest.approx(17.5e-6)
    prog = [S("serve.tick", 10, 90, "a"), S("serve.sync", 20, 50, "a"),
            S("serve.sync.copyback", 30, 50, "a")]
    bench = [E("bench.window", 0, 100), E("bench.tick", 5, 95),
             E("bench.submit", 96, 99)]
    idle = [(0, 100)]
    got = spans.idle_by_span(idle, prog, bench)
    assert got == pytest.approx({
        "serve.tick": 50e-9, "serve.sync.copyback": 20e-9,
        "serve.sync": 10e-9, "bench.tick": 10e-9, "loop": 7e-9,
        "bench.submit": 3e-9})
    assert sum(got.values()) == pytest.approx(100e-9)


def test_stalls_and_summary():
    prog = [S("serve.tick", 0, 900_000_000, "a"),
            S("serve.lookup", 0, 500_000_000, "a"),
            S("serve.tick", 10 ** 9, 10 ** 9 + 5, "a")]
    assert spans.stalls(prog) == [["serve.tick", 900.0, None],
                                  ["serve.lookup", 500.0, "serve.tick"]]
    assert spans.summary(prog)["serve.tick"] == dict(
        n=2, total_ms=pytest.approx(900.000005), max_ms=900.0)


def test_recorded_trace_reads_as_before():
    """The recorded trace has no ``serve.*`` span; the reduction the
    benchmark already reads returns what it always has on it, and idle time
    put down to spans equals the benchmark's own labels there."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(RECORDED))
    assert spans.from_profile(pd) == []
    tr = xplane.from_profile(pd)
    assert sorted(s.name for s in tr.spans) == [
        "bench.tick", "bench.tick", "bench.update"]
    lo = min(s.start_ns for s in tr.spans)
    hi = max(s.end_ns for s in tr.spans)
    assert (lo, hi) == (45186669, 730221106)
    assert xplane.busy_seconds(tr, lo, hi) == pytest.approx(0.072864053,
                                                            rel=1e-9)
    merged = xplane.busy_intervals(tr, "/device:TPU:0", lo, hi)
    idle = xplane.gaps(merged, lo, hi)
    assert (len(merged), len(idle)) == (2, 3)
    labelled = xplane.label_gaps(idle, tr.spans)
    assert labelled == pytest.approx(
        {"update": 2.034e-05, "tick": 0.611373774, "loop": 0.00077627},
        rel=1e-9)
    assert spans.idle_by_span(idle, [], tr.spans) == pytest.approx(
        {f"bench.{k}" if k != "loop" else k: v
         for k, v in labelled.items()}, rel=1e-9)
    assert xplane.program_times(tr, FULL_FORWARD, lo, hi) == \
        pytest.approx([0.030198181], rel=1e-9)
    assert xplane.program_times(tr, SERVE_FORWARD, lo, hi) == \
        pytest.approx([0.042665872], rel=1e-9)
    top = xplane.top_ops(tr, lo, hi, 3)
    assert [n for n, _ in top] == ["%fusion.1 = f32[4194304,7]",
                                   "%fusion = u32[4194304,2]",
                                   "%fusion.3 = s32[22313,4,64]"]
    assert [t for _, t in top] == pytest.approx(
        [0.007538532, 0.007538325, 0.00661569], rel=1e-6)


@pytest.mark.parametrize("cell", ["saint-flickr.fresh",
                                  "saint-flickr.subgraph"])
def test_traced_run_reads_program_spans(cell):
    """Off the chip the window is all idle: each idle instant goes to a
    program span where the host was inside one, and the host-side
    readings are there."""
    res = spans.run_traced(cell, SEED, SECONDS, require_tpu=False,
                           overrides=_small(cell))
    assert res["correct"]
    lines = res["_spans"]
    progs = lines["program_spans"]
    assert progs["serve.tick"]["n"] > 0
    idle = lines["idle_by_span"]
    total = sum(idle.values())
    assert total == pytest.approx(res["device"]["window_s"], rel=1e-6)
    # the engine's ticks are covered by its own spans, not the bench's
    assert idle.get("bench.tick", 0) < 0.05 * sum(
        v for k, v in idle.items() if k.startswith(("serve.", "bench.t")))
    got = lines["layer_readings"]
    if res["_info"]["plan"]["full_cache"]:
        keys = ("sync_ms", "sync_host_ms", "complete_ms", "upload_mb")
        # the whole float32 feature matrix (500 features) per refresh
        nodes = res["_info"]["plan"]["nodes"]
        assert got["upload_mb"] == pytest.approx(nodes * 500 * 4 / 1e6)
    else:
        keys = ("khop_ms", "frdc_build_ms", "extract_stage_ms",
                "launch_ms", "upload_mb")
    for k in keys:
        assert got[k] > 0, k
