"""What `BENCHMARK.json` names, found by name in the benchmark's files.

* a cell (`workloads` entry) names a configuration and a traffic mix;
* a configuration is the JSON file its `configs` entry names;
* a traffic mix is `<bench>/traffic/<traffic>.json`;
* a per-layer metric is a reader `<bench>/metrics/<metric>.py` that
  defines `read(run)` and returns a number, or None where it finds
  nothing to read;
* a plain reference is `<bench>/references/<name>.py`, named by the
  configuration's `"reference"` key (`paper` where it names none). It
  defines `results(config, traffic, points, ftype, device)`: one summary
  dict per sweep point, with every key the program reports for it.

Adding a cell, a configuration, a traffic mix, a metric or a reference
means adding files and entries; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
DEFAULT_REFERENCE = "paper"


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    workloads: Optional[List[str]] = None

    def applies(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]
    bench_dir: str


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _metrics(entries, cell: str) -> List[Metric]:
    out = [Metric(e["name"], e["unit"], e.get("workloads")) for e in entries]
    return [m for m in out if m.applies(cell)]


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell `name` with its configuration, traffic and metrics."""
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    bench_dir = os.path.join(root, spec["paths"][0])
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        traffic_name=w["traffic"],
        config=load_json(os.path.join(root, configs[w["config"]]["file"])),
        traffic=load_json(os.path.join(bench_dir, "traffic",
                                       f"{w['traffic']}.json")),
        end_to_end=_metrics(spec["end_to_end"], name),
        per_layer=_metrics(spec["per_layer"], name),
        bench_dir=bench_dir)


def _module(bench_dir: str, kind: str, name: str):
    """The module `<bench>/<kind>/<name>.py`."""
    path = os.path.join(bench_dir, kind, f"{name}.py")
    mod_name = f"bench_{kind}_" + "".join(
        c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(bench_dir: str, name: str) -> Callable:
    """The `read` function of `<bench>/metrics/<name>.py`."""
    return _module(bench_dir, "metrics", name).read


def reference(cell: Cell) -> Callable:
    """The `results` function of the cell's plain reference."""
    name = cell.config.get("reference", DEFAULT_REFERENCE)
    return _module(cell.bench_dir, "references", name).results


def read_metrics(cell: Cell, run) -> Dict[str, dict]:
    """Every per-layer metric of the cell that finds something to read."""
    out = {}
    for m in cell.per_layer:
        value = metric_reader(cell.bench_dir, m.name)(run)
        if value is not None:
            out[m.name] = {"value": value, "unit": m.unit}
    return out
