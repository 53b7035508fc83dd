"""Finds a cell's parts by name, from ``BENCHMARK.json`` at the root of
a checkout: the configuration file it names, the traffic mix
``bench/traffic/<traffic>.json``, the plain reference
``bench/references/<reference>.py`` and the family module
``bench/families/<family>.py`` that the configuration names (its mapping
onto the program and its work counts; ``bench/families/dense.py`` says
what one gives), and one reader ``bench/metrics/<metric>.py`` for each
metric the cell reports.  Adding any of these is adding files and
entries; nothing here changes."""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType

BENCH = "bench"


@dataclass
class Cell:
    root: Path
    name: str
    chips: int
    config: dict
    mix: dict
    metrics: list[dict]               # BENCHMARK.json metric entries
    readers: dict[str, ModuleType] = field(default_factory=dict)
    reference: ModuleType | None = None
    family: ModuleType | None = None


def load_module(path: Path) -> ModuleType:
    """Import one file by path (metric names may hold dots)."""
    if not path.is_file():
        raise FileNotFoundError(f"{path} not found")
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reports(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load(root: Path, workload: str, trace: bool) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    mix = json.loads((root / BENCH / "traffic" / f"{w['traffic']}.json")
                     .read_text())
    metrics = [m for m in bench["per_layer" if trace else "end_to_end"]
               if reports(m, workload)]
    cell = Cell(root=root, name=workload, chips=w["chips"], config=config,
                mix=mix, metrics=metrics)
    cell.readers = {m["name"]: load_module(
        root / BENCH / "metrics" / f"{m['name']}.py") for m in metrics}
    cell.reference = load_module(
        root / BENCH / "references" / f"{config['reference']}.py")
    cell.family = load_module(
        root / BENCH / "families" / f"{config['family']}.py")
    return cell
