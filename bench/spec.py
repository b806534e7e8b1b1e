"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; a metric names itself.
Each lives in a file of its own under ``bench/``:

* ``configs/<config>.json``   — the configuration as it is run (sizes,
  deployment, precision, the reference module that checks it);
* ``reference/<module>.py``    — the plain float32 reference a config names;
* ``traffic/<traffic>.json``   — parameters for the one generator
  (``traffic.py``);
* ``metrics/<metric>.py``      — one reader per metric, ``read(run)``;
* ``limits/<cell>.json``       — the limits of the numbers ``correct``
  compares, with the readings each was set from.

A new cell, configuration, mix or metric is therefore new files plus new
``BENCHMARK.json`` entries; no existing file changes.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    known = ", ".join(e["name"] for e in entries)
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json "
                   f"(known: {known})")


def _json(sub: str, name: str) -> dict:
    path = BENCH / sub / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"{path} is missing")
    return json.loads(path.read_text())


def _module(sub: str, name: str):
    path = BENCH / sub / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"{path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"bench_{sub}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One ``workloads`` entry with its configuration, traffic, metric
    readers and limits resolved."""

    def __init__(self, name: str, bench: dict | None = None):
        bench = bench or load_benchmark()
        self.bench = bench
        self.entry = _by_name(bench["workloads"], name, "workload")
        self.name = name
        self.chips = int(self.entry["chips"])
        cfg_entry = _by_name(bench["configs"], self.entry["config"],
                             "config")
        self.config = json.loads((ROOT / cfg_entry["file"]).read_text())
        self.traffic = _json("traffic", self.entry["traffic"])
        self.limits = _json("limits", name)

    @classmethod
    def of(cls, bench: dict, name: str, config: dict, traffic: dict,
           limits: dict) -> "Cell":
        """A cell from given parts (tests run tiny configurations)."""
        cell = cls.__new__(cls)
        cell.bench, cell.name = bench, name
        cell.entry = _by_name(bench["workloads"], name, "workload")
        cell.chips = int(cell.entry["chips"])
        cell.config, cell.traffic, cell.limits = config, traffic, limits
        return cell

    def metrics(self, trace: bool) -> list:
        """The metric entries this cell reports: its end-to-end metrics
        with ``--trace 0``, its per-layer metrics with ``--trace 1``.  A
        metric without a ``workloads`` key is reported in every cell that
        reports the end-to-end metric it moves (per-layer) or in every
        cell (end-to-end)."""
        e2e = [m for m in self.bench["end_to_end"]
               if self.name in m.get("workloads", [self.name])]
        if not trace:
            return e2e
        mine = {m["name"] for m in e2e}
        return [m for m in self.bench["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in mine)]

    def reader(self, metric: str):
        return _module("metrics", metric)

    def reference(self):
        return _module("reference", self.config["reference"])

