"""Finds a cell's parts as files, by the names ``BENCHMARK.json`` gives.

``configs/<config>.json``   a configuration, as it is run
``traffic/<traffic>.json``  a traffic mix's parameters (``workload.py``)
``limits/<cell>.json``      the limits of the cell's comparison, with the
                            readings they were set from
``metrics/<metric>.py``     a per-layer metric: ``read(ctx)`` returns a
                            number, or None when it finds nothing to read
``arch/<arch>.py``          the architecture a configuration names with
                            ``"arch"``: its seeded parameter tree, its plain
                            reference and fp8 control, its operation count

A new configuration, architecture, mix or metric is a new file and a new
entry in ``BENCHMARK.json``; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent


class Registry:
    def __init__(self, bench: Dict, base: Path = HERE):
        self.bench = bench
        self.base = Path(base)
        self._modules: Dict[str, ModuleType] = {}

    @classmethod
    def from_file(cls, path: Path, base: Path = HERE) -> "Registry":
        return cls(json.loads(Path(path).read_text()), base)

    def _json(self, kind: str, name: str) -> Dict:
        path = self.base / kind / f"{name}.json"
        if not path.is_file():
            raise FileNotFoundError(f"no {kind} file {path}")
        return json.loads(path.read_text())

    def cell(self, name: str) -> Dict:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> Dict:
        return self._json("configs", name)

    def traffic(self, name: str) -> Dict:
        return self._json("traffic", name)

    def limits(self, cell: str) -> Dict:
        return self._json("limits", cell)

    def _module(self, kind: str, name: str) -> ModuleType:
        path = self.base / kind / f"{name}.py"
        if str(path) not in self._modules:
            spec = importlib.util.spec_from_file_location(
                f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"),
                path)
            if spec is None or not path.is_file():
                raise FileNotFoundError(f"no {kind} module {path}")
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            self._modules[str(path)] = mod
        return self._modules[str(path)]

    def metric(self, name: str) -> Callable:
        """The reader of a per-layer metric: ``read(ctx)``."""
        return self._module("metrics", name).read

    def arch(self, name: str) -> ModuleType:
        """An architecture's module: ``make_params(m, w, seed, dtype)``,
        ``label_logits(cfg, seed, prompts, labels, quant=None)`` and
        ``request_flops(m, computed, cached)``."""
        return self._module("arch", name)

    def _reported(self, entry: Dict, cell: str,
                  e2e_of_cell: Optional[List[str]] = None) -> bool:
        if "workloads" in entry:
            return cell in entry["workloads"]
        return e2e_of_cell is None or entry.get("moves") in e2e_of_cell

    def end_to_end(self, cell: str) -> List[Dict]:
        return [m for m in self.bench["end_to_end"]
                if self._reported(m, cell)]

    def per_layer(self, cell: str) -> List[Dict]:
        e2e = [m["name"] for m in self.end_to_end(cell)]
        return [m for m in self.bench["per_layer"]
                if self._reported(m, cell, e2e)]
