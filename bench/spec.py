"""Finds every piece of a cell by its name in ``BENCHMARK.json``.

A configuration is ``bench/configs/<config>.json``, a traffic mix
``bench/traffic/<traffic>.json``, a query ``bench/queries/<q>.py`` and a
per-layer metric ``bench/metrics/<metric>.py``.  Adding one of them is
adding a file and an entry; nothing here names any of them.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def config(name: str) -> dict:
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def traffic(name: str) -> dict:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def query(name: str) -> ModuleType:
    return importlib.import_module(f"bench.queries.{name}")


def metric_reader(name: str):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    mod_name = "bench.metrics." + name.replace(".", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return table[device_kind]


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    mix: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _listed(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def cell(name: str, bench: dict = None) -> Cell:
    bench = bench if bench is not None else benchmark()
    by_name: Dict[str, dict] = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(by_name)}")
    w = by_name[name]
    e2e = [m for m in bench["end_to_end"] if _listed(m, name)]
    names = {m["name"] for m in e2e}
    per_layer = [
        m for m in bench["per_layer"] if _listed(m, name) and m["moves"] in names
    ]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config_name=w["config"],
        config=config(w["config"]),
        mix=traffic(w["traffic"]),
        end_to_end=e2e,
        per_layer=per_layer,
    )
