"""BENCHMARK.json and the files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name in the manifest:

  configuration  <file given in the manifest>
  traffic mix    <paths[0]>/traffic/<traffic>.json, naming its generator
  generator      <paths[0]>/generators/<generator>.py
  layer metric   <paths[0]>/layers/<metric name>.py
  world builder  <paths[0]>/worlds/<configuration's "world_builder">.py:
                 build_world(params, seed) and to_program(world)
  reference      <paths[0]>/references/<configuration's "reference">.py:
                 Reference(world, keep_policy=None); it may bring its own
                 failed_statements(sample) (correct.py)

A configuration that names neither reads <paths[0]>/world.py and
<paths[0]>/reference.py, the first deployments' own.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class Manifest:
    def __init__(self, path: str = os.path.join(ROOT, "BENCHMARK.json")):
        self.path = path
        self.root = os.path.dirname(os.path.abspath(path))
        with open(path) as f:
            self.doc = json.load(f)
        self.home = os.path.join(self.root, self.doc["paths"][0])

    def cell(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in {self.path}: have "
                       f"{[w['name'] for w in self.doc['workloads']]}")

    def config(self, name: str) -> dict:
        entry = next(c for c in self.doc["configs"] if c["name"] == name)
        return load_json(os.path.join(self.root, entry["file"]))

    def traffic_path(self, name: str) -> str:
        return os.path.join(self.home, "traffic", f"{name}.json")

    def generator_path(self, name: str) -> str:
        return os.path.join(self.home, "generators", f"{name}.py")

    def layer_path(self, metric: str) -> str:
        return os.path.join(self.home, "layers", f"{metric}.py")

    def world_path(self, config: dict) -> str:
        return self._named(config, "world_builder", "worlds", "world.py")

    def reference_path(self, config: dict) -> str:
        return self._named(config, "reference", "references", "reference.py")

    def _named(self, config: dict, key: str, folder: str, default: str):
        if key not in config:
            return os.path.join(self.home, default)
        return os.path.join(self.home, folder, f"{config[key]}.py")

    def metrics_of(self, cell: str, group: str) -> list:
        """The metrics of `group` (end_to_end | per_layer) this cell is
        asked for: those without a `workloads` key, or naming the cell."""
        return [m for m in self.doc[group]
                if "workloads" not in m or cell in m["workloads"]]


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    name = "bench_" + os.path.basename(path)[:-3].replace(".", "_").replace(
        "-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # a module that defines dataclasses is looked up
    spec.loader.exec_module(mod)
    return mod
