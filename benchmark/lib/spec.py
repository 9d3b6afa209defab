"""Find a cell by name: its workload entry, its configuration file, its
traffic file and the metric readers it reports. Everything here is found
by the names in ``BENCHMARK.json``; adding a cell, a configuration, a
traffic mix or a metric adds files and entries and edits nothing."""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class SpecError(ValueError):
    """The benchmark's files do not describe the cell asked for."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list = field(default_factory=list)  # metric entries
    per_layer: list = field(default_factory=list)


def load_benchmark(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"{path} is missing")
    return json.loads(path.read_text())


def _reported_in(metric: dict, workload: str) -> bool:
    cells = metric.get("workloads")
    return cells is None or workload in cells


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"(have {sorted(by_name)})")
    work = by_name[name]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[work["config"]]
    config = json.loads((root / entry["file"]).read_text())
    traffic_path = root / "benchmark" / "traffic" / f"{work['traffic']}.json"
    traffic = json.loads(traffic_path.read_text())
    return Cell(
        name=name,
        chips=int(work["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _reported_in(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reported_in(m, name)],
    )


def metric_reader(metric_name: str, root: Path = ROOT) -> Callable:
    """``read(ctx)`` of ``benchmark/metrics/<metric_name>.py``."""
    path = root / "benchmark" / "metrics" / f"{metric_name}.py"
    if not path.is_file():
        raise SpecError(f"no reader {path} for metric {metric_name!r}")
    mod_name = "bench_metric_" + metric_name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
