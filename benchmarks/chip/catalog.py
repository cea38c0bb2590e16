"""Finds the benchmark's parts by the names `BENCHMARK.json` gives them.

Every configuration, cell, traffic mix, traffic driver, kernel count and
metric reader is a file of its own under this directory, found by its
name, so a later change adds one by adding a file:

  configs/<config>.json      sizes and knobs of one configuration (the
                             `file` its BENCHMARK.json entry names)
  workloads/<cell>.json      one cell: pages, check sample
  traffic/<mix>.json         one traffic mix: its driver and parameters
  traffic/<driver>.py        a generator of traffic (`drive`)
  references/<name>.py       a plain reference (`scores`)
  systems/<name>.py          how the index is built and searched on the
                             cell's chips (`CHIPS`, `build`, `compile`),
                             named by a configuration's "system", else
                             `one_chip`
  kernels/<kernel>.py        a kernel's trace pattern and its counts
  metrics/<metric>.py        one metric's reader (`read`); a metric split
                             by its cells' end-to-end metric, such as
                             `adc_roofline.open`, may share the reader
                             `metrics/adc_roofline.py`
"""
from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


class Catalog:
    """The parts under `root`, a checkout's `benchmarks/chip`."""

    def __init__(self, root: str = HERE, benchmark: dict | None = None):
        self.root = root
        self.checkout = os.path.dirname(os.path.dirname(root))
        if benchmark is None:
            with open(os.path.join(self.checkout, "BENCHMARK.json")) as f:
                benchmark = json.load(f)
        self.benchmark = benchmark

    def json(self, kind: str, name: str) -> dict:
        with open(os.path.join(self.root, kind, f"{name}.json")) as f:
            return json.load(f)

    def module(self, kind: str, name: str):
        path = os.path.join(self.root, kind, f"{name}.py")
        if kind == "metrics" and not os.path.exists(path):
            path = os.path.join(self.root, kind,
                                f"{name.rsplit('.', 1)[0]}.py")
        spec = importlib.util.spec_from_file_location(
            f"benchmarks.chip.{kind}.{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def config(self, name: str) -> dict:
        """The configuration `name`, from the file its entry names."""
        entry = next(c for c in self.benchmark["configs"]
                     if c["name"] == name)
        with open(os.path.join(self.checkout, entry["file"])) as f:
            return json.load(f)

    def system(self, config: dict):
        """The system module the configuration names (`"system"`), by
        default `systems/one_chip.py`."""
        return self.module("systems", config.get("system", "one_chip"))

    def cell(self, name: str) -> dict:
        """The cell's entry in BENCHMARK.json, merged with its files:
        {entry, workload, config, mix, end_to_end, per_layer}, the
        metrics that list the cell."""
        entries = [w for w in self.benchmark["workloads"]
                   if w["name"] == name]
        if not entries:
            raise KeyError(f"no cell {name!r} in BENCHMARK.json")
        entry = entries[0]
        return {"entry": entry,
                "workload": self.json("workloads", name),
                "config": self.config(entry["config"]),
                "mix": self.json("traffic", entry["traffic"]),
                "end_to_end": [m for m in self.benchmark["end_to_end"]
                               if name in m.get("workloads", [name])],
                "per_layer": [m for m in self.benchmark["per_layer"]
                              if name in m["workloads"]]}
