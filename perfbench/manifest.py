"""``BENCHMARK.json`` and the files it names, found by name and nothing else.

A cell is a ``workloads`` entry: a configuration (``configs[].file``), a traffic
mix (``<path>/traffic/<traffic>.json``), the cell's limits for ``correct``
(``<path>/limits/<cell>.json``) and the per-layer metrics that list it, each
read by ``<path>/layer_metrics/<metric>.py``. ``<path>`` is any directory in
``paths``, so a later PR brings a cell in a directory of its own.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parent.parent


class Cell:
    def __init__(self, manifest: Dict[str, Any], name: str, root: Path = ROOT) -> None:
        self.root = root
        self.manifest = manifest
        found = [w for w in manifest["workloads"] if w["name"] == name]
        if not found:
            known = ", ".join(w["name"] for w in manifest["workloads"])
            raise KeyError(f"no workload {name!r} in BENCHMARK.json (has: {known})")
        self.workload = found[0]
        self.name = name
        self.chips = int(self.workload["chips"])
        entry = [c for c in manifest["configs"] if c["name"] == self.workload["config"]]
        if not entry:
            raise KeyError(f"workload {name!r} names no known config")
        self.config_entry = entry[0]
        config_file = root / self.config_entry["file"]
        self.config = _load_json(config_file if config_file.is_file() else ROOT / self.config_entry["file"])
        self.mix_path = self.find(f"traffic/{self.workload['traffic']}.json")
        self.mix = _load_json(self.mix_path)
        self.limits = _load_json(self.find(f"limits/{name}.json"))

    def find(self, relative: str) -> Path:
        for base in self.manifest["paths"]:
            for root in (self.root, ROOT):  # a manifest kept elsewhere still finds the harness
                candidate = root / base / relative
                if candidate.is_file():
                    return candidate
        raise FileNotFoundError(f"{relative} is under none of {self.manifest['paths']}")

    def _listed(self, metric: Dict[str, Any], moved_ok: bool) -> bool:
        if "workloads" in metric:
            return self.name in metric["workloads"]
        return moved_ok

    def end_to_end(self) -> List[Dict[str, Any]]:
        return [m for m in self.manifest["end_to_end"] if self._listed(m, True)]

    def per_layer(self) -> List[Dict[str, Any]]:
        mine = {m["name"] for m in self.end_to_end()}
        return [m for m in self.manifest["per_layer"] if self._listed(m, m["moves"] in mine)]

    def reader(self, metric: str) -> Callable[[Dict[str, Any]], Optional[float]]:
        path = self.find(f"layer_metrics/{metric}.py")
        spec = importlib.util.spec_from_file_location(
            "perfbench_reader_" + metric.replace(".", "_").replace("-", "_"), path
        )
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read


def _load_json(path: Path) -> Dict[str, Any]:
    with open(path) as fh:
        return json.load(fh)


def load(root: Path = ROOT) -> Dict[str, Any]:
    return _load_json(root / "BENCHMARK.json")
