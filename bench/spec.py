"""What a run reads from disk: ``BENCHMARK.json`` and the files it names.

Every configuration, traffic mix, cell limit and metric lives in a file of its
own, found by its name:

* ``configs[].file``            — the model's sizes as run, with its
  ``family``;
* ``bench/families/<family>.py`` — that kind of model: its ``Shape``, weight
  layout, reference layers, FLOP count and the program's ``ArchConfig``
  (see ``bench/families/dense.py``);
* ``bench/traffic/<traffic>.json`` — the federated job's parameters;
* ``bench/limits/<cell>.json``  — the limits of the comparison that decides
  ``correct`` in that cell;
* ``bench/metrics/<metric>.py`` — a reader ``read(run) -> float | None``.

A new cell, configuration, model family, traffic mix or metric is a new file
and an entry in ``BENCHMARK.json``; no code here changes.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os
import re
import sys
from dataclasses import dataclass
from types import ModuleType
from typing import Callable

from . import ROOT


def family(name: str, root: str = ROOT) -> ModuleType:
    """The module ``bench/families/<name>.py`` under ``root``, loaded once
    per file (its ``Shape`` class is a ``jax.jit`` static argument)."""
    return _load_family(os.path.abspath(os.path.join(root, "bench", "families", name + ".py")))


@functools.cache
def _load_family(path: str) -> ModuleType:
    name = "bench_family_" + re.sub(r"\W", "_", path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod        # dataclasses and ``family_of`` look it up
    spec.loader.exec_module(mod)
    return mod


def family_of(shape) -> ModuleType:
    """The family module whose ``Shape`` ``shape`` is."""
    return sys.modules[type(shape).__module__]


def __getattr__(name: str):
    # ``ModelShape``: the dense family's shape under its older name
    if name == "ModelShape":
        return family("dense").Shape
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


@dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with the files it names."""

    name: str
    chips: int
    config_name: str
    config: dict
    traffic: dict
    limits: dict
    family: ModuleType    # bench/families/<config's "family">.py

    @property
    def shape(self):
        return self.family.Shape.from_config(self.config)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = _json(os.path.join(root, configs[w["config"]]["file"]))
    bench_dir = os.path.join(root, "bench")
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"], config=cfg,
        traffic=_json(os.path.join(bench_dir, "traffic", w["traffic"] + ".json")),
        limits=_json(os.path.join(bench_dir, "limits", name + ".json")),
        family=family(cfg["family"], root),
    )


def metrics_for(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metric entries a run of ``cell`` reports: its end-to-end metrics
    untraced, its per-layer metrics traced.  A metric without a
    ``workloads`` key belongs to every cell."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def metric_reader(name: str, root: str = ROOT) -> Callable:
    """``read(run) -> float | None`` from ``bench/metrics/<name>.py``."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str, root: str = ROOT) -> dict:
    """The chip's published peaks; a device missing from the table is an error."""
    table = _json(os.path.join(root, "bench", "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in bench/peaks.json "
                       f"(have {sorted(table['devices'])})")
    return table["devices"][device_kind]
