"""Finds what belongs to a cell by the names in ``BENCHMARK.json``.

    configuration  ->  its ``file`` (as ``BENCHMARK.json`` gives it)
    traffic        ->  benchmark/traffic/<traffic>.json
    cell           ->  benchmark/cells/<cell>.json      (limits of ``correct``)
    metric         ->  benchmark/metrics/<metric>.py    (``read(ctx)``)
    reference      ->  benchmark/reference/<name>.py    (``loss``, ``extras``)

No name of a cell, a configuration or a metric is written in code.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import types
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    name = "bench_" + os.path.relpath(path, HERE).replace(os.sep, "_")[:-3]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod     # flax and dataclasses look a class's module up
    spec.loader.exec_module(mod)
    return mod


def merge(base: Dict[str, Any], over: Dict[str, Any]) -> Dict[str, Any]:
    """``over`` on top of ``base``, dictionaries merged key by key."""
    out = dict(base)
    for k, v in over.items():
        out[k] = (merge(out[k], v)
                  if isinstance(v, dict) and isinstance(out.get(k), dict)
                  else v)
    return out


class Context(types.SimpleNamespace):
    """What a metric's reader is handed: the cell, its files, the window
    (stamps, losses, host spans), the trace (traced runs), set-up seconds,
    the device's peak memory and the sizes of the job."""


class Bench:
    """``BENCHMARK.json`` with the files its names point to. ``root`` is
    the checkout; ``home`` the directory that holds traffic/, cells/,
    metrics/ and reference/ (the first of ``paths``)."""

    def __init__(self, path: str = None):
        self.path = path or os.path.join(ROOT, "BENCHMARK.json")
        self.root = os.path.dirname(os.path.abspath(self.path))
        self.spec = load_json(self.path)
        self.home = os.path.join(self.root, self.spec["paths"][0])

    def cell(self, name: str) -> Dict[str, Any]:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in {self.path}; it has "
                       f"{[w['name'] for w in self.spec['workloads']]}")

    def config(self, name: str) -> Dict[str, Any]:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return load_json(os.path.join(self.root, c["file"]))
        raise KeyError(f"no configuration {name!r} in {self.path}")

    def traffic(self, name: str) -> Dict[str, Any]:
        return load_json(os.path.join(self.home, "traffic", name + ".json"))

    def limits(self, cell: str) -> Dict[str, Any]:
        return load_json(os.path.join(self.home, "cells", cell + ".json"))[
            "limits"]

    def reference(self, name: str):
        return load_module(os.path.join(self.home, "reference", name + ".py"))

    def metrics(self, group: str, cell: str) -> List[Dict[str, Any]]:
        """The metrics of ``end_to_end`` or ``per_layer`` that this cell
        reports, each with its reader under ``read``."""
        out = []
        for m in self.spec[group]:
            if "workloads" in m and cell not in m["workloads"]:
                continue
            mod = load_module(os.path.join(self.home, "metrics",
                                           m["name"] + ".py"))
            out.append(dict(m, read=mod.read))
        return out
