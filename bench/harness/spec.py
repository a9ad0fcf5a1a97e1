"""Find a cell and everything it names, by name, from BENCHMARK.json.

Nothing here knows a particular cell: a configuration is the JSON file its
entry names, a traffic mix is ``<bench dir>/traffic/<traffic>.json`` and a
per-layer metric is read by ``<bench dir>/layers/<metric>.py`` (a module
with ``read(ctx)``).  The bench dir is the first of ``paths`` that holds
the file, so a cell added by new files alone is found without an edit.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, List


class SpecError(ValueError):
    pass


@dataclass
class Cell:
    root: Path
    bench: dict
    workload: dict
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    def layer_reader(self, metric: str) -> Callable:
        path = _find(self.root, self.bench, f"layers/{metric}.py")
        spec = importlib.util.spec_from_file_location(
            f"bench_layer_{metric.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


def _find(root: Path, bench: dict, rel: str) -> Path:
    for p in bench.get("paths", []):
        cand = root / p / rel
        if cand.is_file():
            return cand
    raise SpecError(f"no {rel} under any of {bench.get('paths')}")


def _applies(metric: dict, cell: str, reported: set) -> bool:
    names = metric.get("workloads")
    if names is not None:
        return cell in names
    return metric.get("moves", metric["name"]) in reported


def load_cell(root: Path, workload: str) -> Cell:
    """The cell named ``workload`` with its configuration, traffic mix and
    the metrics it reports."""
    root = Path(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"unknown workload {workload!r}; "
                        f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    centry = configs[w["config"]]
    config = json.loads((root / centry["file"]).read_text())
    traffic = json.loads(_find(root, bench,
                               f"traffic/{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"]
           if m.get("workloads") is None or workload in m["workloads"]]
    reported = {m["name"] for m in e2e}
    layers = [m for m in bench["per_layer"]
              if _applies(m, workload, reported)]
    return Cell(root, bench, w, config, traffic, e2e, layers)
