"""Find a cell, its configuration, its traffic driver and the per-layer
readers by NAME: BENCHMARK.json is the registry, and whatever belongs
to one configuration, one traffic kind, one cell or one per-layer metric
sits in a file of its own under benchmark/.  A later PR adds any of
them by adding files and entries; no file here is edited for it.

  configs/<config>.json     the deployment as it is run (BENCHMARK.json
                            names the file)
  workloads/<cell>.json     the cell's traffic mix: the parameters one
                            general driver reads (``driver`` names it;
                            by default the mix's own name)
  traffic/<kind>.py         the driver: set_up / run / check / tear_down
  layers/<metric>.py        read(obs) -> value or None
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys


class BenchError(Exception):
    """The benchmark's own files do not fit together."""


class Cell:
    def __init__(self, entry: dict, params: dict, config: dict):
        self.name: str = entry["name"]
        self.config_name: str = entry["config"]
        self.traffic: str = entry["traffic"]
        self.chips: int = int(entry["chips"])
        self.why: str = entry["why"]
        self.params = params
        self.config = config
        # a new mix for a driver that is there is data alone: its file
        # names the driver; a mix named after its driver need not
        self.driver: str = params.get("driver", self.traffic)

    def param(self, key: str, default=None):
        """A traffic parameter: the cell's file first, then the
        configuration's ``fixed`` and ``assumed`` groups."""
        for group in (self.params, self.config.get("fixed", {}),
                      self.config.get("assumed", {})):
            if key in group:
                return group[key]
        if default is None:
            raise BenchError(
                f"cell {self.name}: parameter {key!r} is in neither "
                f"its file nor configuration {self.config_name}")
        return default


class Bench:
    def __init__(self, root: str):
        self.root = root
        self.dir = os.path.join(root, "benchmark")
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.manifest = json.load(f)

    def _json(self, *parts: str) -> dict:
        path = os.path.join(*parts)
        try:
            with open(path) as f:
                return json.load(f)
        except FileNotFoundError as e:
            raise BenchError(f"missing file {path}") from e

    def _module(self, kind: str, name: str):
        path = os.path.join(self.dir, kind, name + ".py")
        if not os.path.exists(path):
            raise BenchError(f"missing file {path}")
        # loaded by path, so a copy of the benchmark elsewhere (the
        # loader's own test) gets its own files, not this checkout's
        mod_name = "_bench_%s_%s_%x" % (
            kind, "".join(c if c.isalnum() else "_" for c in name),
            abs(hash(path)))
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod
        spec.loader.exec_module(mod)
        return mod

    def cell_names(self) -> list[str]:
        return [w["name"] for w in self.manifest["workloads"]]

    def cell(self, name: str) -> Cell:
        for entry in self.manifest["workloads"]:
            if entry["name"] == name:
                break
        else:
            raise BenchError(
                f"no workload {name!r} in BENCHMARK.json "
                f"(have: {', '.join(self.cell_names())})")
        params = self._json(self.dir, "workloads", name + ".json")
        return Cell(entry, params, self.config(entry["config"]))

    def config(self, name: str) -> dict:
        for entry in self.manifest["configs"]:
            if entry["name"] == name:
                return self._json(self.root, entry["file"])
        raise BenchError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, kind: str):
        return self._module("traffic", kind)

    def reader(self, metric: str):
        return self._module("layers", metric)

    def metrics(self, group: str, cell: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics a cell reports:
        those with no ``workloads`` list, or with the cell in it."""
        return [m for m in self.manifest[group]
                if "workloads" not in m or cell in m["workloads"]]
