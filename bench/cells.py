"""Resolve a workload of ``BENCHMARK.json`` into the files it names."""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
from pathlib import Path
from typing import Any, Callable, Dict, List

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    spec: Dict[str, Any]            # the BENCHMARK.json entry


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]          # bench/configs/<config>.json
    traffic: Dict[str, Any]         # bench/traffic/<traffic>.json
    end_to_end: List[Metric]        # reported with --trace 0
    per_layer: List[Metric]         # reported with --trace 1

    @property
    def traffic_name(self) -> str:
        return self.traffic["name"]


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def traffic_file(name: str, bench: Path = BENCH) -> Path:
    return bench / "traffic" / f"{name}.json"


def metric_file(name: str, bench: Path = BENCH) -> Path:
    return bench / "metrics" / f"{name}.py"


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The workload ``name`` with its configuration, mix and metrics."""
    spec = load_benchmark(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    check_mesh(config, int(w["chips"]))
    traffic = json.loads(traffic_file(w["traffic"], root / "bench")
                         .read_text())
    traffic.setdefault("name", w["traffic"])
    e2e = [Metric(m["name"], m["unit"], m["better"], m["source"], m)
           for m in spec["end_to_end"] if _applies(m, name)]
    layer = [Metric(m["name"], m["unit"], m["better"], m["source"], m)
             for m in spec["per_layer"] if _applies(m, name)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, layer)


def check_mesh(config: Dict[str, Any], chips: int) -> None:
    """A configuration's ``mesh`` ({axis: size}) has to span exactly the
    cell's chips.  A configuration without one runs on one device."""
    mesh = config.get("mesh")
    if mesh is None:
        return
    n = math.prod(int(v) for v in mesh.values())
    if n != chips:
        raise ValueError(f"configuration {config.get('name')!r}: mesh "
                         f"{mesh} spans {n} devices, the cell asks for "
                         f"{chips} chips")


def bench_module(config: Dict[str, Any], role: str):
    """The module a configuration names for ``role`` ("weights" or
    "reference"): ``bench/<name>.py``, by default ``bench/<role>.py``."""
    return importlib.import_module(f"bench.{config.get(role, role)}")


def metric_reader(name: str, bench: Path = BENCH) -> Callable[[Any], Any]:
    """``read(run)`` of ``bench/metrics/<name>.py``: the metric's value
    from a finished run, or None where the run holds nothing to read."""
    path = metric_file(name, bench)
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
