"""Where a cell's data lives, and how a name in a data file becomes
code.  Nothing here knows any one configuration, mix or metric.

A cell is an entry of ``workloads`` in ``BENCHMARK.json`` (or, for the
CPU rehearsal only, of ``chipbench/rehearsal/workloads.json``), and its
metrics are those of ``BENCHMARK.json`` that have no ``workloads`` list
or name the cell on it: a PR that adds a cell appends its name there.
Its
``config`` names ``configs/<config>.json``, its ``traffic`` names
``traffic/<traffic>.json``, and each of its per-layer metrics names
``layer_metrics/<metric>.py``.  Imports no jax.
"""

from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
REHEARSAL = os.path.join(HERE, "rehearsal")


def resolve(path: str):
    """``"package.module:callable"`` -> the callable."""
    module, _, name = path.partition(":")
    return getattr(importlib.import_module(module), name)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _load(os.path.join(CHECKOUT, "BENCHMARK.json"))


def rehearsal() -> dict:
    return _load(os.path.join(REHEARSAL, "workloads.json"))


class Cell:
    """One cell with everything its files say."""

    def __init__(self, name: str):
        bench = benchmark()
        entry = next((w for w in bench["workloads"] if w["name"] == name),
                     None)
        self.rehearsal = entry is None
        base, appended = HERE, {}
        if entry is None:
            # Rehearsal cells are never listed in BENCHMARK.json, so the
            # driver never runs them, and they name their device `cpu`.
            # Their file holds what a PR adding them would have added
            # to BENCHMARK.json: the entries, and their names appended
            # to the lists of the metrics they report.
            table = rehearsal()
            entry = next((w for w in table["workloads"]
                          if w["name"] == name), None)
            base, appended = REHEARSAL, table["appended"]
        if entry is None:
            raise SystemExit(f"no cell named {name!r} in BENCHMARK.json or "
                             f"the rehearsal table")
        self.name, self.chips = name, int(entry["chips"])
        self.entry = entry
        self.config = _load(os.path.join(
            base, "configs", entry["config"] + ".json"))
        self.traffic = _load(os.path.join(
            base, "traffic", entry["traffic"] + ".json"))
        self.run_seconds = bench["run_seconds"]

        def mine(metric: dict) -> bool:
            return "workloads" not in metric or name in (
                metric["workloads"] + appended.get(metric["name"], []))

        self.end_to_end = [m for m in bench["end_to_end"] if mine(m)]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if mine(m) and m["moves"] in reported]

    def driver(self):
        """``traffic.kind`` names ``chipbench/drivers/<kind>.py``."""
        return importlib.import_module(
            "chipbench.drivers." + self.traffic["kind"])


def peaks(device_kind: str) -> dict:
    table = _load(os.path.join(HERE, "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it "
                       f"to chipbench/peaks.json with its source")
    return table["devices"][device_kind]
