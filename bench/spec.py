"""Find a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix or one metric
is a file of its own, found by name:

* ``bench/configs/<config>.json``   sizes, precisions, limits of a model
* ``bench/traffic/<traffic>.json``  parameters of a traffic mix
* ``bench/metrics/<metric>.py``     the reader of one metric (``read(run)``)
* ``bench/families/<family>.py``    operations and bytes a block family needs
* ``bench/reference/<family>.py``   the plain reference of a block family

So a new cell, mix or metric is new files plus entries in ``BENCHMARK.json``;
nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List

ROOT = Path(__file__).resolve().parents[1]


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path) -> ModuleType:
    """Import one file by path (metric readers, families, references)."""
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    spec = importlib.util.spec_from_file_location(f"bench_file_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    def __init__(self, name: str, root: Path = ROOT):
        self.root = Path(root)
        self.bench = load_json(self.root / "BENCHMARK.json")
        by_name = {w["name"]: w for w in self.bench["workloads"]}
        if name not in by_name:
            raise KeyError(f"unknown workload {name!r}; known: {sorted(by_name)}")
        self.workload = by_name[name]
        self.name = name
        configs = {c["name"]: c for c in self.bench["configs"]}
        self.config_entry = configs[self.workload["config"]]
        self.config = load_json(self.root / self.config_entry["file"])
        self.mix = load_json(self.root / "bench" / "traffic" / f"{self.workload['traffic']}.json")
        self.chips = int(self.workload["chips"])

    def metrics(self, kind: str) -> List[Dict]:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports.

        An entry with a ``workloads`` key is reported in the cells it lists.
        An end-to-end entry without one is reported everywhere; a per-layer
        entry without one wherever its ``moves`` metric is reported.
        """
        if kind == "end_to_end":
            return [m for m in self.bench["end_to_end"] if self._listed(m)]
        reported = {m["name"] for m in self.metrics("end_to_end")}
        return [
            m
            for m in self.bench["per_layer"]
            if self._listed(m) and ("workloads" in m or m["moves"] in reported)
        ]

    def _listed(self, m: Dict) -> bool:
        return "workloads" not in m or self.name in m["workloads"]

    def reader(self, metric: str) -> ModuleType:
        return load_module(self.root / "bench" / "metrics" / f"{metric}.py")

    def family(self) -> ModuleType:
        return load_module(self.root / "bench" / "families" / f"{self.config['family']}.py")

    def reference(self) -> ModuleType:
        return load_module(self.root / "bench" / "reference" / f"{self.config['family']}.py")

    def peaks(self, device_kind: str) -> Dict:
        table = load_json(self.root / "bench" / "peaks.json")
        if device_kind not in table["devices"]:
            raise KeyError(f"device {device_kind!r} is not in bench/peaks.json")
        return table["devices"][device_kind]
