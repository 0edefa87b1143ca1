"""Find a cell's configuration, traffic and metrics by the names in
``BENCHMARK.json``. Nothing here knows a particular cell: a new cell is a new
entry there plus data files under ``configs/`` and ``traffic/``, and a new
per-layer metric is a new reader under ``metrics/``. A new architecture adds
a module under ``tpubench/archs/`` (``tpubench.arch`` says what it provides),
a configuration file that names it in its ``"arch"``, and a traffic file."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    workloads: Optional[List[str]]
    moves: Optional[str] = None
    layer: Optional[str] = None

    def applies_to(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict  # the configuration file, as run
    traffic: dict  # the traffic file
    end_to_end: List[Metric]
    per_layer: List[Metric]

    @property
    def tp(self) -> int:
        """The tensor-parallel degree the configuration states (its
        ``"parallel": {"tp": n}``; 1 where it states none)."""
        return int(self.config.get("parallel", {}).get("tp", 1))


def _metrics(entries, cell: str) -> List[Metric]:
    out = []
    for e in entries:
        m = Metric(name=e["name"], unit=e["unit"], better=e["better"],
                   source=e["source"], workloads=e.get("workloads"),
                   moves=e.get("moves"), layer=e.get("layer"))
        if m.applies_to(cell):
            out.append(m)
    return out


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, bench_path: Optional[str] = None) -> Cell:
    bench = load_json(bench_path or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(ROOT, configs[w["config"]]["file"]))
    traffic = load_json(os.path.join(BENCH_DIR, "traffic", f"{w['traffic']}.json"))
    cell = Cell(name=name, chips=int(w["chips"]), config=config, traffic=traffic,
                end_to_end=_metrics(bench["end_to_end"], name),
                per_layer=_metrics(bench["per_layer"], name))
    if cell.chips != cell.tp:
        raise ValueError(f"cell {name} asks for {cell.chips} chip(s), but its configuration "
                         f"{w['config']} states tensor parallelism over {cell.tp}")
    return cell


def load_reader(metric: str) -> Callable:
    """``metrics/<metric>.py``'s ``read(run) -> float | None``."""
    path = os.path.join(BENCH_DIR, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(f"tpubench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
