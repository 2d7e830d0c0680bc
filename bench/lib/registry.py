"""Finds a cell's parts by the names in ``BENCHMARK.json``.

  * a configuration: the ``file`` its entry names (``bench/configs/``);
  * a traffic mix: ``bench/traffic/<traffic>.json``;
  * a per-layer metric: ``bench/metrics/<name>.py``, or, for a name with a
    suffix such as ``.tail``, the reader of the part before the first dot;
  * a unit's operation and byte counts: ``bench/work/<unit>.py``;
  * a cell's correctness limits: ``bench/limits/<cell>.json``;
  * the peaks: ``bench/peaks.json``, by ``device_kind``.

A later cell, mix or metric is one more file; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Any, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[2]


def _load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_work(unit: str, root: Path = ROOT):
    return _load_module(root / "bench" / "work" / f"{unit}.py")


class Registry:
    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.bench = json.loads((self.root / "BENCHMARK.json").read_text())
        self.dir = self.root / "bench"

    def _json(self, path: Path) -> Dict[str, Any]:
        if not path.is_file():
            raise FileNotFoundError(f"no file {path}")
        return json.loads(path.read_text())

    def cell(self, name: str) -> Dict[str, Any]:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> Dict[str, Any]:
        for c in self.bench["configs"]:
            if c["name"] == name:
                return self._json(self.root / c["file"])
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def mix(self, traffic: str) -> Dict[str, Any]:
        return self._json(self.dir / "traffic" / f"{traffic}.json")

    def limits(self, cell: str) -> Dict[str, Any]:
        return self._json(self.dir / "limits" / f"{cell}.json")

    def peaks(self, device_kind: str) -> Optional[Dict[str, float]]:
        return self._json(self.dir / "peaks.json").get(device_kind)

    def reader(self, metric: str):
        path = self.dir / "metrics" / f"{metric}.py"
        if not path.is_file():
            path = self.dir / "metrics" / f"{metric.split('.')[0]}.py"
        return _load_module(path).read

    def _reports(self, entry: Dict[str, Any], cell: str,
                 e2e: List[str]) -> bool:
        if "workloads" in entry:
            return cell in entry["workloads"]
        return entry.get("moves", entry["name"]) in e2e

    def end_to_end(self, cell: str) -> List[Dict[str, Any]]:
        return [m for m in self.bench["end_to_end"]
                if "workloads" not in m or cell in m["workloads"]]

    def per_layer(self, cell: str) -> List[Dict[str, Any]]:
        e2e = [m["name"] for m in self.end_to_end(cell)]
        return [m for m in self.bench["per_layer"]
                if self._reports(m, cell, e2e)]
