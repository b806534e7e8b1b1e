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

A configuration file carries the whole architecture it runs, under the
published ``config.json`` names.  Its keys are of two kinds:

* harness keys (``HARNESS_KEYS``: the name, the sources, what was cut and
  assumed, ``published``, ``leaf_roles``, the program entry, the
  reference, the deployment, the precision), which never reach the
  program;
* architecture keys, each of which ``system.program_config`` hands to the
  program's ``ModelConfig`` in exactly one way: renamed
  (``system.PUBLISHED_TO_PROGRAM``), under its own name where that is a
  ``ModelConfig`` field (``head_dim``, ``rope_theta``, ...), or matched
  against a value the program implements without a field
  (``system.IMPLIED``: ``scoring_func`` "softmax", ``rope_scaling`` null,
  ...).  Any other key, or another value, is refused by name before a
  weight is made, as is a field of the program's registry entry that
  holds architecture the file leaves out.  ``work.py`` counts operations
  and bytes from the same keys, and refuses one whose effect on the
  counts it does not model.

A cut is stated: every key in ``reduced`` has its published value in
``published``, and every key there is in ``reduced``.  An expert share
(the ``model-configs`` guide, section 4) lists ``n_routed_experts`` in
``reduced``: the file's count is the experts this chip holds, experts
``[0, held)``, ``published.n_routed_experts`` is the router's width, and
``deployment.expert_parallel`` is the number of chips that share each
layer.  ``leaf_roles`` (``{leaf name: role}``) names the served leaves
the default table of ``weights.py`` does not.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent

HARNESS_KEYS = frozenset({"name", "source", "paper", "model_type", "reduced",
                          "assumed", "published", "leaf_roles", "program",
                          "reference", "deployment", "precision"})


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

