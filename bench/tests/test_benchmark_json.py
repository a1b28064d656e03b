"""BENCHMARK.json against the benchmark's contract, and every file it names."""
import json
import os
import re

import pytest

from bench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WIDTHS = re.compile(r"(_size$|_dim$|_rank$|^head|latent|state|proj|expan|per_tok)")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    cells = len(bench["workloads"])
    assert 2 + 14 * 24 * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert cells <= 24 and os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) < 65536


def test_names_units_and_files(bench):
    names = []
    for c in bench["configs"]:
        names.append(c["name"])
        cfg = json.load(open(os.path.join(spec.ROOT, c["file"])))
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        assert not [k for k in c["reduced"] if WIDTHS.search(k)]
        assert c["file"].startswith("bench/")
    for w in bench["workloads"]:
        names.append(w["name"])
        cell = spec.load_cell(w["name"])
        assert cell.chips == 1 and len(w["why"]) <= 200
        assert set(cell.limits) >= {"rounds", "loss_gap", "grad_gap", "change_gap",
                                    "input_mismatch", "nonfinite_rounds",
                                    "compiles_in_window"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert os.path.exists(os.path.join(spec.ROOT, "bench", "metrics", m["name"] + ".py"))
    assert all(NAME.match(n) for n in names) and len(names) == len(set(names))


def test_every_cell_reports_what_its_metrics_move(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e and all(0.01 <= m["bound"] <= 0.25 for m in bench["end_to_end"])
    for w in bench["workloads"]:
        mine = {m["name"] for m in spec.metrics_for(bench, w["name"], trace=False)}
        assert "setup_s" in mine and len(mine) >= 2
        layer = spec.metrics_for(bench, w["name"], trace=True)
        assert layer and all(m["moves"] in mine for m in layer)
