"""``BENCHMARK.json`` and the files it names. Everything that belongs to
one configuration, one traffic mix, one runner kind or one per-layer
metric sits in a file of its own, found here by name — a later PR adds
files and entries and edits nothing."""

from __future__ import annotations

import importlib
import json
import os
import re
from typing import Any, Callable, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


class Manifest:
    """The benchmark as data, read from the checkout ``root`` (the
    directory above this file: a copy of ``benchmarks/`` elsewhere reads
    its own ``BENCHMARK.json``, which is how a test shows that adding
    needs no edit)."""

    def __init__(self, root: str = ROOT) -> None:
        self.root = root
        self.bench_dir = os.path.join(root, "benchmarks")
        self.doc = load_json(os.path.join(root, "BENCHMARK.json"))

    # -- look-ups by name ---------------------------------------------------

    def cell(self, name: str) -> Dict[str, Any]:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(
            f"no workload {name!r} in BENCHMARK.json (has: "
            f"{[w['name'] for w in self.doc['workloads']]})")

    def config_entry(self, name: str) -> Dict[str, Any]:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return c
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def config(self, name: str, rehearsal: bool = False) -> Dict[str, Any]:
        path = os.path.join(self.root, self.config_entry(name)["file"])
        if rehearsal:
            path = path[:-len(".json")] + ".rehearsal.json"
        return load_json(path)

    def traffic(self, mix: str) -> Dict[str, Any]:
        return load_json(os.path.join(self.bench_dir, "traffic",
                                      mix + ".json"))

    def metric_file(self, name: str) -> Dict[str, Any]:
        return load_json(os.path.join(self.bench_dir, "metrics",
                                      name + ".json"))

    def metrics_of(self, cell: str, group: str) -> List[Dict[str, Any]]:
        """The ``end_to_end`` or ``per_layer`` metrics ``cell``
        reports (no ``workloads`` key means every cell)."""
        return [m for m in self.doc[group]
                if cell in m.get("workloads", [cell])]


def bench_module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py`` — a runner by its traffic mix's
    ``kind``, a reference by its configuration's name."""
    if not NAME_RE.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    return importlib.import_module(f"benchmarks.{kind}.{name}")


def resolve(dotted: str) -> Callable:
    """``package.module.function`` -> the function (a per-layer
    metric's reader)."""
    mod, _, fn = dotted.rpartition(".")
    return getattr(importlib.import_module(mod), fn)
