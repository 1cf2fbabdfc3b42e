"""Finds everything that belongs to a cell by name, from data files.

``BENCHMARK.json`` names the cell's configuration and traffic mix; the
rest is found on disk under the benchmark's directory:

  configs/<file named in BENCHMARK.json>   sizes of the configuration
  traffic/<traffic>.json                    parameters of the mix, and the
                                            ``driver`` that generates it
  workloads/<cell>.json                     settings of this one cell: load,
                                            engine settings, check limits
  drivers/<driver>.py                       general generator + timed loop
  metrics/<metric>.py                       one reader per per-layer metric
  reference/<reference>.py                  plain reference of a config

A new cell, configuration or metric is therefore new files plus a new
entry in ``BENCHMARK.json``; no existing file changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from types import ModuleType
from typing import Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _read_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict
    traffic: Dict
    settings: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    bench_dir: str

    def module(self, kind: str, name: str) -> ModuleType:
        return load_module(self.bench_dir, kind, name)

    @property
    def driver(self) -> ModuleType:
        return self.module("drivers", self.traffic["driver"])


def load_module(bench_dir: str, kind: str, name: str) -> ModuleType:
    """Import ``<bench_dir>/<kind>/<name>.py`` under a private name (file
    names may hold dots, as metric names do)."""
    path = os.path.join(bench_dir, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} module {name!r} at {path}")
    mod_name = f"_bench_{kind}_{name}".replace(".", "_").replace("-", "_")
    cached = sys.modules.get(mod_name)
    if cached is not None and getattr(cached, "__file__", None) == path:
        return cached
    spec = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = module
    spec.loader.exec_module(module)
    return module


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``."""
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    entry = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[entry["config"]]
    bench_dir = os.path.join(root, bench["paths"][0])
    traffic = _read_json(os.path.join(bench_dir, "traffic",
                                      f"{entry['traffic']}.json"))
    return Cell(
        name=name, chips=int(entry["chips"]), config_name=entry["config"],
        traffic_name=entry["traffic"],
        config=_read_json(os.path.join(root, cfg_entry["file"])),
        traffic=traffic,
        settings=_read_json(os.path.join(bench_dir, "workloads",
                                         f"{name}.json")),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        bench_dir=bench_dir)
