"""Resolve a cell of ``BENCHMARK.json`` to its files, by name alone.

A cell ``<config>.<traffic>`` reads ``deployments/<config>.json`` and
``traffic/<traffic>.json``; the deployment names its ``driver``, the
module ``drivers/<driver>.py`` that builds and runs the system.  A metric
``<name>`` is read by ``e2e_metrics/<name>.py`` or
``layer_metrics/<name>.py``, or, where that file does not exist, by the
file of the part of the name before its first dot (``x.open`` and
``x.saturate`` share ``x.py``).  Adding a configuration, a mix, a driver
or a metric is adding its file and its entries; no code here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys
from types import ModuleType
from typing import Dict, List

BENCH = pathlib.Path(__file__).resolve().parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    e2e: List[dict]           # end-to-end metrics this cell reports
    layer: List[dict]         # per-layer metrics this cell reports


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(root: pathlib.Path, name: str,
            bench: pathlib.Path = BENCH) -> Cell:
    spec = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    return Cell(name=name, chips=w["chips"],
                config=_json(bench / "deployments" / f"{w['config']}.json"),
                traffic=_json(bench / "traffic" / f"{w['traffic']}.json"),
                e2e=[m for m in spec["end_to_end"] if _applies(m, name)],
                layer=[m for m in spec["per_layer"] if _applies(m, name)])


_MODULES: Dict[pathlib.Path, ModuleType] = {}


def _load(path: pathlib.Path) -> ModuleType:
    mod = _MODULES.get(path)
    if mod is None:
        spec = importlib.util.spec_from_file_location(
            f"bench_{path.parent.name}_{path.stem.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = mod
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return mod


def reader(kind: str, metric: str, bench: pathlib.Path = BENCH
           ) -> ModuleType:
    """The module whose ``read(run)`` gives ``metric`` (``kind`` is
    ``e2e_metrics`` or ``layer_metrics``)."""
    for stem in (metric, metric.split(".", 1)[0]):
        path = bench / kind / f"{stem}.py"
        if path.is_file():
            return _load(path)
    raise FileNotFoundError(f"no reader for {metric!r} under {kind}/")


def driver(name: str, bench: pathlib.Path = BENCH) -> ModuleType:
    return _load(bench / "drivers" / f"{name}.py")
