"""Find a cell's configuration, traffic and metrics by name.

``BENCHMARK.json`` names every cell, configuration and metric; the files
that define them sit under this directory and are looked up by those names,
so adding a cell, a configuration or a metric adds a file and edits none.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    read: Callable[[Any], Optional[float]]


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]      # configs/<config>.json
    traffic: Dict[str, Any]     # workloads/<cell>.json
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _load_reader(path: Path) -> Callable[[Any], Optional[float]]:
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _metrics(entries, cell: str, bench_dir: Path) -> List[Metric]:
    out = []
    for m in entries:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        path = bench_dir / "metrics" / f"{m['name']}.py"
        if not path.is_file():
            raise FileNotFoundError(f"metric {m['name']!r} has no reader"
                                    f" {path}")
        out.append(Metric(m["name"], m["unit"], _load_reader(path)))
    return out


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``, with its files."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench_dir = root / "chipbench"
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known:"
                       f" {sorted(cells)}")
    w = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / confs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (bench_dir / "workloads" / f"{w['traffic']}.json").read_text())
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=_metrics(bench["end_to_end"], name, bench_dir),
                per_layer=_metrics(bench["per_layer"], name, bench_dir))
