"""BENCHMARK.json and the files it names.

A cell names a configuration and a traffic mix; the harness finds
``configs/<config>.json``, ``traffic/<traffic>.json`` and
``layers/<metric>.py`` by those names, so a later PR adds a cell as new
files plus new entries and edits nothing that is here.
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts), encoding="utf-8") as f:
        return json.load(f)


def load_module(*parts: str):
    """A file under ``benchmark/`` as a module, by path: metric names may
    hold ``.`` and ``-``, which an import statement cannot spell."""
    path = os.path.join(HERE, *parts)
    name = "benchmark_" + "_".join(parts).replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with everything its name resolves to."""

    def __init__(self, manifest: dict, name: str):
        found = [w for w in manifest["workloads"] if w["name"] == name]
        if not found:
            known = ", ".join(w["name"] for w in manifest["workloads"])
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json ({known})")
        self.name = name
        self.entry = found[0]
        self.chips = int(self.entry["chips"])
        self.config_name = self.entry["config"]
        self.config = load_json("configs", self.config_name + ".json")
        self.traffic = load_json("traffic", self.entry["traffic"] + ".json")
        self.end_to_end = [
            m for m in manifest["end_to_end"] if self._reports(m)
        ]
        self.per_layer = [
            m for m in manifest["per_layer"] if self._reports(m)
        ]

    def _reports(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def deployment(self):
        return load_module("deployments", self.config["deployment"] + ".py")

    def reference(self):
        return load_module("references", self.config["reference"] + ".py")

    def reader(self, metric_name: str):
        return load_module("layers", metric_name + ".py").read
