"""Find a cell's files by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
harness finds everything else from those names alone:

- the configuration's ``file`` (``configs/<config>.json``), the
  configuration as it is run, and beside it ``<stem>.reference.py``,
  its plain reference;
- ``traffic/<traffic>.json``       the traffic mix; its ``kind`` names
- ``drivers/<kind>.py``            the driver that runs that kind of mix;
- ``metrics/<metric>.py``          one reader per per-layer metric.

A cell, configuration, traffic mix or metric is added by adding files
and entries, without editing a file that is there.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys
from typing import Any, Dict, List, Optional

HERE = pathlib.Path(__file__).resolve().parents[1]        # benchmarks/chip
CHECKOUT = HERE.parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    root: pathlib.Path
    config_file: pathlib.Path

    def reference(self):
        f = self.config_file
        return load_module(f.with_name(f.stem + ".reference.py"))

    def driver(self):
        return load_module(self.root / "drivers" / f"{self.traffic['kind']}.py")


def load_json(path: pathlib.Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    """Import a file of the benchmark by its path (names may hold '-'
    and '.', which ``import`` does not take)."""
    name = "benchchip_" + "".join(
        c if c.isalnum() else "_" for c in str(path.resolve()))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, bench: Optional[Dict[str, Any]] = None,
              checkout: pathlib.Path = CHECKOUT) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its files loaded."""
    bench = bench if bench is not None else load_json(
        checkout / "BENCHMARK.json")
    root = checkout / bench["paths"][0]
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    conf = configs[w["config"]]
    return Cell(
        name=name, config_name=w["config"], traffic_name=w["traffic"],
        chips=int(w["chips"]),
        config=load_json(checkout / conf["file"]),
        config_file=checkout / conf["file"],
        traffic=load_json(root / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        root=root)


def metric_reader(cell: Cell, metric: str):
    return load_module(cell.root / "metrics" / f"{metric}.py")
