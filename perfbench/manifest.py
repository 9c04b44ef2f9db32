"""``BENCHMARK.json`` and the files it names, found by name and nothing else.

A cell is a ``workloads`` entry: a configuration (``configs[].file``), a traffic
mix (``<path>/traffic/<traffic>.json``), the cell's limits for ``correct``
(``<path>/limits/<cell>.json``) and the per-layer metrics that list it, each
read by ``<path>/layer_metrics/<metric>.py``. The configuration names its
architecture's family (``perfbench.family``), whose adapter is
``<path>/families/<family>.py``, and its plain reference
(``perfbench.reference``, a file under a directory of ``paths``). ``<path>`` is
any directory in ``paths``, so a later PR brings a cell, and a model the
harness has never seen, in a directory of its own.

The adapter is the one place that knows an architecture; all of what the
drivers and the readers ask it: ``model(config)``, ``make_params(config, seed,
dtype)``, ``reference_kwargs(config)``, ``architecture_leaves(tree)`` and the
counts ``decode_flops(config, live_lengths)``, ``decode_attention_bytes(config,
live_lengths, kv_bytes, act_bytes)``, ``train_flops_per_token(config,
mean_keys)``. The reference brings ``logits_at`` for serving, ``loss_and_grads``
and ``adamw_step`` for training. A family brings what its cells' kind asks for.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType
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
        self.config = _load_json(self._file(self.config_entry["file"]) or root / self.config_entry["file"])
        self.mix_path = self.find(f"traffic/{self.workload['traffic']}.json")
        self.mix = _load_json(self.mix_path)
        self.limits = _load_json(self.find(f"limits/{name}.json"))

    def _file(self, relative: str) -> Optional[Path]:
        for root in (self.root, ROOT):  # a manifest kept elsewhere still finds the harness
            if (root / relative).is_file():
                return root / relative
        return None

    def find(self, relative: str) -> Path:
        for base in self.manifest["paths"]:
            found = self._file(f"{base}/{relative}")
            if found is not None:
                return found
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
        return load_module(self.find(f"layer_metrics/{metric}.py")).read

    def family(self) -> ModuleType:
        """The adapter of the configuration's architecture."""
        return load_module(self.find(f"families/{self.config['perfbench']['family']}.py"))

    def reference(self) -> ModuleType:
        """The configuration's plain reference, at the path its file gives."""
        relative = self.config["perfbench"]["reference"]
        if not any(relative.startswith(base + "/") for base in self.manifest["paths"]):
            raise FileNotFoundError(f"{relative} is under none of {self.manifest['paths']}")
        found = self._file(relative)
        if found is None:
            raise FileNotFoundError(f"{relative}, the configuration's reference, is no file")
        return load_module(found)


@functools.lru_cache(maxsize=None)
def load_module(path: Path) -> ModuleType:
    """The Python file at ``path`` as a module, by its location and once a
    process (so that what it jits stays compiled). A function that is asked of
    it and missing is an error that names the file and the function."""
    spec = importlib.util.spec_from_file_location("perfbench_file_" + re.sub(r"\W", "_", str(path)), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)

    def missing(name: str) -> Any:
        raise AttributeError(f"{path} brings no {name!r}")

    module.__dict__.setdefault("__getattr__", missing)
    return module


def _load_json(path: Path) -> Dict[str, Any]:
    with open(path) as fh:
        return json.load(fh)


def load(root: Path = ROOT) -> Dict[str, Any]:
    return _load_json(root / "BENCHMARK.json")
