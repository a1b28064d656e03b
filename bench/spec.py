"""What a run reads from disk: ``BENCHMARK.json`` and the files it names.

Every configuration, traffic mix, cell limit and metric lives in a file of its
own, found by its name:

* ``configs[].file``            — the model's sizes as run (``ModelShape``);
* ``bench/traffic/<traffic>.json`` — the federated job's parameters;
* ``bench/limits/<cell>.json``  — the limits of the comparison that decides
  ``correct`` in that cell;
* ``bench/metrics/<metric>.py`` — a reader ``read(run) -> float | None``.

A new cell, configuration, traffic mix or metric is a new file and an entry
in ``BENCHMARK.json``; no code here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Callable

from . import ROOT


@dataclass(frozen=True)
class ModelShape:
    """A dense decoder's sizes, as the configuration file states them."""

    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    qkv_bias: bool
    tied: bool
    rope_theta: float
    norm_eps: float
    dtype: str
    init_std: float

    @classmethod
    def from_config(cls, cfg: dict) -> "ModelShape":
        heads = int(cfg["num_attention_heads"])
        return cls(
            layers=int(cfg["num_hidden_layers"]),
            d_model=int(cfg["hidden_size"]),
            heads=heads,
            kv_heads=int(cfg.get("num_key_value_heads", heads)),
            head_dim=int(cfg.get("head_dim", int(cfg["hidden_size"]) // heads)),
            d_ff=int(cfg["intermediate_size"]),
            vocab=int(cfg["vocab_size"]),
            qkv_bias=bool(cfg["qkv_bias"]),
            tied=bool(cfg["tie_word_embeddings"]),
            rope_theta=float(cfg["rope_theta"]),
            norm_eps=float(cfg["rms_norm_eps"]),
            dtype=str(cfg["torch_dtype"]),
            init_std=float(cfg["assumed"]["init_std"]),
        )


@dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with the files it names."""

    name: str
    chips: int
    config_name: str
    config: dict
    traffic: dict
    limits: dict

    @property
    def shape(self) -> ModelShape:
        return ModelShape.from_config(self.config)


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
