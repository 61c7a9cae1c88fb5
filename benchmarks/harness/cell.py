"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell is one entry of ``workloads``: a configuration under a traffic mix.
Everything that belongs to one configuration, one mix, one cell's limits or
one per-layer metric sits in a file of its own, found here by name, so a
later PR adds files and one entry and edits nothing.

    configs/<config>.json            sizes as run, source, assumed, reduced
    configs/<config>/program.py      builds the program's model for them
    reference/<reference>.py         the plain reference
    traffic/<traffic>.json           the mix's parameters
    entries/<entry>.py               drives one of the program's entry points
    limits/<cell>.json               the limits of ``correct``, with readings
    metrics/<metric>.py              one reader per per-layer metric
"""

from __future__ import annotations

import importlib
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load(path):
    with open(path) as f:
        return json.load(f)


class Cell:
    def __init__(self, name, bench, chips, config, traffic, limits):
        self.name, self.bench, self.chips = name, bench, chips
        self.config, self.traffic, self.limits = config, traffic, limits
        if traffic["chips"] != chips:
            raise SystemExit(f"benchmark: {name}: the mix is laid out for "
                             f"{traffic['chips']} chip(s), the cell asks "
                             f"for {chips}")

    @classmethod
    def load(cls, name: str, root: str = ROOT) -> "Cell":
        bench = _load(os.path.join(root, "BENCHMARK.json"))
        entry = next((w for w in bench["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise SystemExit(f"benchmark: no workload {name!r} in "
                             "BENCHMARK.json")
        conf = next(c for c in bench["configs"]
                    if c["name"] == entry["config"])
        bench_dir = os.path.join(root, bench["paths"][0])
        return cls(
            name, bench, entry["chips"],
            _load(os.path.join(root, conf["file"])),
            _load(os.path.join(bench_dir, "traffic",
                               entry["traffic"] + ".json")),
            _load(os.path.join(bench_dir, "limits",
                               name + ".json"))["limits"])

    def metric_names(self, group: str):
        """The cell's metrics of ``end_to_end`` or ``per_layer``: those with
        no ``workloads`` key, and those that list this cell."""
        return [m["name"] for m in self.bench[group]
                if self.name in m.get("workloads", [self.name])]

    def reference(self):
        return importlib.import_module(
            f"benchmarks.reference.{self.config['reference']}")

    def program(self):
        return importlib.import_module(
            f"benchmarks.configs.{self.config['program']}.program")

    def entry(self):
        return importlib.import_module(
            f"benchmarks.entries.{self.traffic['entry']}").Entry


def metric_reader(name: str):
    """The reader of one per-layer metric: ``read(ctx) -> number or None``."""
    return importlib.import_module(f"benchmarks.metrics.{name}").read
