"""Finds the benchmark's data files by the names in ``BENCHMARK.json``.

A cell ``<name>`` is ``cells/<name>.json``; it names a configuration
(``configs/<config>.json``) and a traffic mix (``traffic/<mix>.json``).
A per-layer metric ``<name>`` is read by ``metrics/<name>.py``, or by
the reader of the part of its name before the first dot; a model
family's plain reference is ``reference/<family>.py`` and its work count
``work/<family>.py``. Nothing here knows any particular cell.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
BENCHMARK = ROOT / "BENCHMARK.json"


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _json(BENCHMARK)


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in {BENCHMARK.name}; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def cell(name: str) -> dict:
    return _json(HERE / "cells" / f"{name}.json")


def config(name: str) -> dict:
    return _json(HERE / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return _json(HERE / "traffic" / f"{name}.json")


def peaks(device_kind: str) -> dict:
    table = _json(HERE / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json; have {sorted(table)}")
    return table[device_kind]


def _applies(metric: dict, cell_name: str, bench: dict) -> bool:
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    moves = metric.get("moves")
    if moves is None:            # an end-to-end metric without a list
        return True
    return any(m["name"] == moves and _applies(m, cell_name, bench)
               for m in bench["end_to_end"])


def end_to_end(bench: dict, cell_name: str) -> List[dict]:
    return [m for m in bench["end_to_end"] if _applies(m, cell_name, bench)]


def per_layer(bench: dict, cell_name: str) -> List[dict]:
    return [m for m in bench["per_layer"] if _applies(m, cell_name, bench)]


def reader(metric_name: str) -> ModuleType:
    """The module that reads a per-layer metric: ``metrics/<name>.py``, or,
    for a name split by the end-to-end metric it moves
    (``forward_mfu.fresh``), the part before the first dot
    (``metrics/forward_mfu.py``). It defines ``read(run)``."""
    for stem in (metric_name, metric_name.split(".")[0]):
        path = HERE / "metrics" / f"{stem}.py"
        if path.exists():
            break
    else:
        raise FileNotFoundError(f"no reader for metric {metric_name!r} in "
                                f"{HERE / 'metrics'}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics.{stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family_module(kind: str, family: str) -> ModuleType:
    """``reference/<family>.py`` or ``work/<family>.py``."""
    return importlib.import_module(f"chipbench.{kind}.{family}")
