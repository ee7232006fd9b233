"""BENCHMARK.json and the files it names: every cell, configuration, mix
and per-layer metric is found by its name, and the file keeps to the
benchmark's contract."""
import json
import re

import numpy as np
import pytest

from chipbench import record, spec

BENCH = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
LAYER_METRICS = [m["name"] for m in BENCH["per_layer"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for word in BENCH["command"]:
        assert not word.startswith("/") and ".." not in word
    assert (spec.ROOT / BENCH["command"][1]).exists()
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_names_units_and_bounds():
    names = [m["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for m in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in BENCH["end_to_end"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files(cell):
    w = spec.workload(BENCH, cell)
    c = spec.cell(cell)
    assert c["config"] == w["config"] and c["traffic"] == w["traffic"]
    assert c["why"] == w["why"]
    cfg = spec.config(c["config"])
    assert cfg["name"] == c["config"]
    mix = spec.traffic(c["traffic"])
    assert mix["mode"] in ("auto", "full", "subgraph")
    assert c["query_rate_per_s"] > 0
    if mix.get("updates"):
        assert c["update_rate_per_s"] > 0
    assert set(c["limits"]) <= {"median_err", "max_err", "missing",
                                "unshown"}
    assert c["limits"]["missing"] == 0
    e2e = [m["name"] for m in spec.end_to_end(BENCH, cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec.per_layer(BENCH, cell)
    spec.family_module("reference", cfg["family"])
    spec.family_module("work", cfg["family"])


def test_config_entries_name_their_files():
    for c in BENCH["configs"]:
        cfg = json.loads((spec.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        assert cfg["reduced"] == c["reduced"]


def _empty_run():
    z = np.zeros(0)
    return record.Run(
        tick_start=z,
        tick_end=z, tick_answered=z, tick_invalidated=z.astype(bool),
        batches=[], stages={"batches": 0}, n_nodes=1,
        indptr=np.zeros(2, np.int64), indices=np.zeros(0, np.int64),
        work=lambda r, e: (0.0, 0.0), peaks={}, full_cache=False,
        trace=None)


@pytest.mark.parametrize("name", LAYER_METRICS)
def test_reader_found_by_name(name):
    m = next(m for m in BENCH["per_layer"] if m["name"] == name)
    mod = spec.reader(name)
    assert m["moves"] in [e["name"] for e in BENCH["end_to_end"]]
    # a reader that finds nothing to read returns nothing, never 0
    assert mod.read(_empty_run()) is None


def test_unknown_reader_is_an_error():
    with pytest.raises(FileNotFoundError):
        spec.reader("no_such_metric")
    with pytest.raises(FileNotFoundError):
        spec.reader("no_such_metric.fresh")


def test_metrics_of_one_quantity_share_a_reader():
    assert spec.reader("forward_mfu.fresh").__file__ == \
        spec.reader("forward_mfu.subgraph").__file__


def test_unknown_device_kind_is_an_error():
    assert spec.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        spec.peaks("TPU v99")

