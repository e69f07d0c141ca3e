"""Finding a cell's pieces by name.

``BENCHMARK.json`` names each cell's configuration and traffic, and each
metric. Every piece is a file of its own under the benchmark's directory,
found by that name alone, so a new configuration, traffic mix or per-layer
metric is a new file and an entry in ``BENCHMARK.json``, with no other
file edited:

- a configuration: the ``file`` its entry gives;
- a traffic mix: ``traffic/<traffic>.json``, its templates
  ``queries/<template>.sql``;
- a per-layer metric: ``metrics/<name>.py``, defining ``read(ctx)``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Mapping

BENCH_DIR = "chipbench"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    root: Path

    @property
    def bench_dir(self) -> Path:
        return self.root / BENCH_DIR

    @property
    def queries_dir(self) -> Path:
        return self.bench_dir / "queries"

    def metric_reader(self, name: str) -> ModuleType:
        path = self.bench_dir / "metrics" / f"{name}.py"
        spec = importlib.util.spec_from_file_location(
            f"chipbench_metric_{name.replace('.', '_')}", path)
        if spec is None or spec.loader is None:
            raise FileNotFoundError(f"no reader for metric {name}: {path}")
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module


def _for_cell(metrics: List[Mapping], cell: str) -> List[Dict]:
    return [dict(m) for m in metrics
            if "workloads" not in m or cell in m["workloads"]]


def load_cell(root: Path, name: str) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files read."""
    root = Path(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((root / BENCH_DIR / "traffic" /
                          f"{w['traffic']}.json").read_text())
    return Cell(name, int(w["chips"]), config, traffic,
                _for_cell(bench["end_to_end"], name),
                _for_cell(bench["per_layer"], name), root)
