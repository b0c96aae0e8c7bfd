"""Find a cell's files by the names in the benchmark file.

Everything that belongs to one configuration, one model family, one traffic
mix, one generator kind or one per-layer metric is a file of its own under
one of the directories the benchmark file lists in ``paths``. A
configuration file names its family, and the family's plain reference
(``references/<family>.py``) and its way into the engine
(``adapters/<family>.py``) are found by that name. A later PR adds a cell,
or an architecture, by adding files and entries; nothing here names a cell,
a mix, a metric or a model.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from typing import Any, Dict, List, Optional


class SpecError(Exception):
    """The benchmark file, or a file it names, is missing or malformed."""


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with the files it resolves to."""

    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    spec: "Spec"

    def reference(self):
        """The plain reference of the configuration's family."""
        return self.spec.load_module(
            "references", self.config["family"] + ".py")

    def adapter(self):
        """The family's way into the engine."""
        return self.spec.load_module(
            "adapters", self.config["family"] + ".py")


class Spec:
    """A parsed benchmark file and the directories it searches."""

    def __init__(self, path: str):
        self.path = os.path.abspath(path)
        self.root = os.path.dirname(self.path)
        try:
            with open(self.path) as f:
                self.data = json.load(f)
        except OSError as e:
            raise SpecError(f"cannot read {path}: {e}") from None
        self.dirs = [os.path.normpath(os.path.join(self.root, p))
                     for p in self.data["paths"]]
        self._modules: Dict[str, Any] = {}

    def find(self, *parts: str) -> str:
        """The first ``<dir>/<parts...>`` that exists over ``paths``."""
        for d in self.dirs:
            p = os.path.join(d, *parts)
            if os.path.exists(p):
                return p
        raise SpecError(
            f"no {os.path.join(*parts)} under any of {self.data['paths']}")

    def load_json(self, *parts: str) -> Dict[str, Any]:
        with open(self.find(*parts)) as f:
            return json.load(f)

    def load_module(self, *parts: str):
        """Import ``<dir>/<parts...>`` by path (a metric's name may hold
        characters a module name may not), once for this benchmark file: a
        reference's jitted parts compile once a process."""
        path = self.find(*parts)
        if path not in self._modules:
            name = "_bench_" + "_".join(parts).replace(".", "_") \
                .replace("-", "_")
            mod_spec = importlib.util.spec_from_file_location(name, path)
            mod = importlib.util.module_from_spec(mod_spec)
            sys.modules[name] = mod     # a dataclass looks its module up
            mod_spec.loader.exec_module(mod)
            self._modules[path] = mod
        return self._modules[path]

    def _reported_in(self, metric: Dict[str, Any], cell_name: str,
                     e2e_names: Optional[List[str]] = None) -> bool:
        if "workloads" in metric:
            return cell_name in metric["workloads"]
        # A per-layer metric without the key is read wherever the
        # end-to-end metric it moves is reported.
        return e2e_names is None or metric["moves"] in e2e_names

    def cell(self, name: str) -> Cell:
        for w in self.data["workloads"]:
            if w["name"] == name:
                break
        else:
            raise SpecError(
                f"no workload {name!r} in {self.path} (known: "
                f"{[w['name'] for w in self.data['workloads']]})")
        for c in self.data["configs"]:
            if c["name"] == w["config"]:
                break
        else:
            raise SpecError(f"workload {name!r} names no known config")
        with open(os.path.join(self.root, c["file"])) as f:
            config = json.load(f)
        if not isinstance(config.get("family"), str):
            raise SpecError(
                f"{c['file']} names no family: give it a \"family\" key, the "
                f"name of its references/<family>.py and adapters/<family>.py")
        traffic = self.load_json("traffic", w["traffic"] + ".json")
        e2e = [m for m in self.data["end_to_end"]
               if self._reported_in(m, name)]
        e2e_names = [m["name"] for m in e2e]
        per_layer = [m for m in self.data["per_layer"]
                     if self._reported_in(m, name, e2e_names)]
        return Cell(name=name, chips=int(w["chips"]), config=config,
                    traffic=traffic, end_to_end=e2e, per_layer=per_layer,
                    spec=self)
