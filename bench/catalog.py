"""Finds everything of a cell by name: ``BENCHMARK.json`` at the checkout
root, and under ``bench/`` one file per configuration, traffic mix, set of
limits and metric.  Adding a cell, a mix or a metric means adding files
and entries; no file here changes.

* ``configs/<config>.json``: the model's published sizes and the system's
  settings (``system.entry`` names the module under ``entries/`` that
  runs it, ``reference`` the plain reference under ``reference/``);
* ``traffic/<mix>.json``: parameters for ``generator.py`` or the entry;
* ``limits/<workload>.json``: the limit of each number the check compares;
* ``metrics/<metric>.py``: ``read(run) -> float | None`` for one metric;
* ``reference/<module>.py``: the plain reference of a configuration's
  architecture (the interface the checks use is in ``reference/model.py``).
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import sys
from types import ModuleType
from typing import Callable, Dict, List, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class Catalog:
    """The benchmark's definition and the files it names."""

    def __init__(self, root: pathlib.Path = ROOT,
                 bench_dir: Optional[pathlib.Path] = None):
        self.root = pathlib.Path(root)
        self.dir = pathlib.Path(bench_dir) if bench_dir else BENCH_DIR
        self.doc = json.loads((self.root / "BENCHMARK.json").read_text())
        self._references: Dict[str, ModuleType] = {}

    def _json(self, kind: str, name: str) -> dict:
        path = self.dir / kind / f"{name}.json"
        if not path.is_file():
            raise KeyError(f"no {kind} file {path.relative_to(self.root)}")
        return json.loads(path.read_text())

    def workload(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        known = [w["name"] for w in self.doc["workloads"]]
        raise KeyError(f"unknown workload {name!r}; known: {known}")

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name)

    def limits(self, workload: str) -> dict:
        return self._json("limits", workload)

    def metrics(self, workload: str, per_layer: bool) -> List[dict]:
        """The end-to-end (or per-layer) metrics that ``workload`` reports.

        A metric with a ``workloads`` key applies to the cells it lists;
        an end-to-end metric without one to every cell; a per-layer metric
        without one to every cell that reports the metric it ``moves``."""
        e2e = [m["name"] for m in self.doc["end_to_end"]
               if workload in m.get("workloads", [workload])]
        if not per_layer:
            return [m for m in self.doc["end_to_end"] if m["name"] in e2e]
        return [m for m in self.doc["per_layer"]
                if (workload in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]

    def reader(self, metric: str) -> Callable:
        """``read(run)`` of ``metrics/<metric>.py``."""
        return self._module("metrics", metric).read

    def reference(self, config: dict) -> ModuleType:
        """The plain reference module that a configuration names under
        ``reference``: ``reference/<module>.py``, ``model`` where it names
        none.  Loaded once per catalog, so that its jitted functions
        compile once in a process."""
        name = config.get("reference", "model")
        if name not in self._references:
            self._references[name] = self._module("reference", name)
        return self._references[name]

    def _module(self, kind: str, name: str) -> ModuleType:
        path = self.dir / kind / f"{name}.py"
        if not path.is_file():
            raise KeyError(f"no {kind} module {path.relative_to(self.root)}")
        mod_name = f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}"
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod  # dataclasses look their module up there
        spec.loader.exec_module(mod)
        return mod
