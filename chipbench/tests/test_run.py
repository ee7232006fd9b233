"""A whole run of each cell at a small size on the CPU, past the look for
a chip: sound, it is correct; with the timed path broken underneath, or
with the bfloat16 control in the program's place, it is not."""
import json

import pytest

from chipbench import faults, run, spec

BENCH = spec.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
SMALL = {"config": {"dataset": {"name": "flickr", "seed": 0,
                                "scale": 0.03}}}
SECONDS = 1.0
SEED = 2 ** 31 + 11


def _small(cell):
    c = spec.cell(cell)
    fresh = spec.traffic(c["traffic"]).get("updates")
    rates = ({"query_rate_per_s": 2000.0, "update_rate_per_s": 20.0}
             if fresh else {"query_rate_per_s": 300.0})
    return {**SMALL, "cell": rates}


def _run(cell, **kw):
    return run.run_cell(cell, SEED, SECONDS, False, require_tpu=False,
                        overrides=_small(cell), **kw)


def _fault_cases():
    out = []
    for cell in CELLS:
        fresh = spec.traffic(spec.cell(cell)["traffic"]).get("updates")
        for name in faults.FAULTS:
            if name == "refresh_skipped" and not fresh:
                continue
            out.append((cell, name))
    return out


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_control_is_not(cell):
    res = _run(cell, control=True)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    # the control, judged against the cell's limits as a run is
    assert res["_info"]["control_correct"] is False, res["_info"]["control"]
    assert list(res)[-2] == "checks"       # last key of the result line


@pytest.mark.parametrize("cell,fault", _fault_cases())
def test_broken_timed_path_is_not_correct(cell, fault):
    res = _run(cell, patch=faults.FAULTS[fault])
    assert not res["correct"], (fault, res["checks"])


def test_refuses_without_a_chip(capsys):
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds",
                   "1"])
    assert rc == 2
    out = capsys.readouterr().out
    assert not [ln for ln in out.splitlines() if ln.startswith("{")]


def test_result_line_is_the_last_line(capsys):
    res = _run(CELLS[0])
    run.print_result(res)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    obj = json.loads(last)
    assert list(obj)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(obj)[-1] == "checks"
    assert set(obj["metrics"]) == {m["name"] for m in
                                   spec.end_to_end(BENCH, CELLS[0])}


def test_non_finite_numbers_print_as_null():
    import numpy as np
    got = run.finite({"a": float("inf"), "b": [np.float32(1.5),
                                                 float("nan")],
                      "c": {"d": np.int64(3)}})
    assert got == {"a": None, "b": [1.5, None], "c": {"d": 3}}
    json.dumps(got, allow_nan=False)


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reads_its_layers(cell):
    """Off the chip the trace holds no device plane: the device readers
    return nothing and are left out, the host ones are read."""
    res = run.run_cell(cell, SEED, SECONDS, True, require_tpu=False,
                       overrides=_small(cell))
    assert res["correct"]
    assert res["device"]["window_s"] > 0
    assert res["device"]["busy_s"] is None
    names = {m["name"]: m for m in BENCH["per_layer"]}
    for name, m in res["metrics"].items():
        assert cell in names[name]["workloads"]
        assert names[name]["source"] != "device_trace"
        assert m["value"] > 0
    assert res["metrics"], "no host-side layer metric was read"
    gaps = dict(res["breakdown"]["idle_gaps"])
    assert gaps.get("tick", 0) > 0
