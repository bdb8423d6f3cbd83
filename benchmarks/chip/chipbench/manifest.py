"""``BENCHMARK.json`` and the files it names, found by name.

Each piece of a cell is a file of its own under the benchmark's directory:

    configs/<config>.json     a configuration (the file BENCHMARK.json names)
    traffic/<traffic>.json    a traffic mix: parameters for its kind's driver
    limits/<workload>.json    the limit of every number ``correct`` compares
    metrics/<metric>.py       the reader of one per-layer metric

A later change adds a cell, a configuration or a metric by adding files and
entries; no file here needs an edit.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: Dict[str, dict]
    end_to_end: List[dict]
    per_layer: List[dict]


class Manifest:
    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.bench_dir = self.root / BENCH_DIR.relative_to(ROOT)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())
        self.workloads = {w["name"]: w for w in self.spec["workloads"]}
        self.configs = {c["name"]: c for c in self.spec["configs"]}

    def _json(self, path: Path) -> dict:
        if not path.is_file():
            raise FileNotFoundError(f"benchmark file {path} is missing")
        return json.loads(path.read_text())

    def cell(self, name: str) -> Cell:
        if name not in self.workloads:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                           f"{sorted(self.workloads)}")
        w = self.workloads[name]
        config = self._json(self.root / self.configs[w["config"]]["file"])
        traffic = self._json(self.bench_dir / "traffic" / f"{w['traffic']}.json")
        limits = self._json(self.bench_dir / "limits" / f"{name}.json")

        def applies(m: dict, reported: Optional[set] = None) -> bool:
            if "workloads" in m:
                return name in m["workloads"]
            return reported is None or m["moves"] in reported

        e2e = [m for m in self.spec["end_to_end"] if applies(m)]
        reported = {m["name"] for m in e2e}
        per_layer = [m for m in self.spec["per_layer"] if applies(m, reported)]
        return Cell(name, int(w["chips"]), config, traffic, limits["numbers"],
                    e2e, per_layer)

    def reader(self, metric: str) -> Callable:
        """The ``read(run)`` function of ``metrics/<metric>.py``."""
        path = self.bench_dir / "metrics" / f"{metric}.py"
        if not path.is_file():
            raise FileNotFoundError(f"no reader {path} for metric {metric!r}")
        spec = importlib.util.spec_from_file_location(
            f"chipbench_metric_{metric.replace('.', '_')}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read
