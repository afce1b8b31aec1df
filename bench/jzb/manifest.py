"""``BENCHMARK.json`` and the files it names, found by name.

Each configuration, traffic mix, model reference, kernel count and
per-layer metric reader is a file of its own:

    bench/configs/<config>.json       (the path is the manifest's ``file``)
    bench/traffic/<traffic>.json
    bench/models/<model>.py           (``model`` key of the configuration)
    bench/kernels/<kernel>.py
    bench/metrics/<metric>.py         (defines ``read(run) -> float | None``)

so a later change adds a cell, a configuration or a metric by adding
files and manifest entries, without editing one that is there.
"""
from __future__ import annotations

import importlib.util
import json
import re
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load(kind: str, name: str):
    """Import ``bench/<kind>/<name>.py`` under a private module name."""
    path = BENCH / kind / f"{name}.py"
    mod_name = "jzb_" + kind + "_" + re.sub(r"\W", "_", name)
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


class Manifest:
    def __init__(self, path: Path = ROOT / "BENCHMARK.json"):
        self.doc = json.loads(Path(path).read_text())
        self.cells = {w["name"]: w for w in self.doc["workloads"]}
        self.configs = {c["name"]: c for c in self.doc["configs"]}

    def cell(self, name: str) -> dict:
        if name not in self.cells:
            raise SystemExit(f"unknown workload {name!r}; known: "
                             f"{sorted(self.cells)}")
        return self.cells[name]

    def config(self, name: str) -> dict:
        return json.loads((ROOT / self.configs[name]["file"]).read_text())

    @staticmethod
    def traffic(name: str) -> dict:
        return json.loads((BENCH / "traffic" / f"{name}.json").read_text())

    def end_to_end(self, cell: str) -> list:
        return [m for m in self.doc["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str) -> list:
        """The per-layer metrics this cell reports: those that list it, and
        those without a list whose ``moves`` the cell reports."""
        e2e = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.doc["per_layer"]
                if cell in m.get("workloads", [cell] if m["moves"] in e2e
                                 else [])]

    @staticmethod
    def reader(metric: str):
        return load("metrics", metric).read
