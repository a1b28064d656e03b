"""The DeepSeek-V2 cell's per-layer readers on a record of known scope times,
and the family's scope names on the reduction's list."""
from types import SimpleNamespace

import jax
import pytest

from bench import scopes, spec


def _run(**scope_s):
    cell = spec.load_cell("dsv2lite-xdev-equal")
    return SimpleNamespace(scopes={"scopes": scope_s}, computed_steps=16,
                           tokens_per_step=2 * 512, cell=cell)


def test_family_names_its_scopes_for_the_reduction():
    fam = spec.family("deepseek_v2")
    assert set(fam.SCOPES) <= scopes.SCOPES
    hlo = ('  %dot.1 = f32[2]{0} dot(), metadata={op_name="jit(f)/local_step/blocks/moe/'
           'transpose(jvp(experts))/ragged_dot"}\n')
    assert scopes.scope_map(hlo)["dot.1"] == {"local_step", "blocks", "moe", "experts"}


def test_scope_readers_give_ms_per_computed_step():
    run = _run(moe=0.32, experts=0.16, moe_dispatch=0.048, moe_combine=0.016, mla=0.4)
    read = {n: spec.metric_reader(n)(run) for n in
            ("moe_ms", "experts_ms", "moe_dispatch_ms", "mla_ms")}
    assert read == pytest.approx({"moe_ms": 20.0, "experts_ms": 10.0,
                                  "moe_dispatch_ms": 4.0, "mla_ms": 25.0})


def test_experts_roofline_is_the_bandwidth_bound_time_over_experts_ms(monkeypatch):
    """At 96 rows an expert (768 a layer) the grouped matmuls are bound by
    HBM: 415.2 MB of weights and gradients and 31.8 MB of rows a layer at
    819 GB/s, 0.5458 ms, against 0.2026 ms of bf16 FLOPs; 8 layers."""
    monkeypatch.setattr(jax, "devices", lambda *a: [SimpleNamespace(device_kind="TPU v5 lite")])
    per_layer = (3 * 8 * 3 * 2048 * 1408 * 2 + 768 * (2 * 2048 + 2 * 1408) * 2 * 3) / 819e9
    t_roof = 8 * per_layer
    assert t_roof == pytest.approx(8 * 0.5458e-3, rel=1e-3)
    read = spec.metric_reader("experts_roofline")
    assert read(_run(experts=16 * t_roof)) == pytest.approx(100.0)
    assert read(_run(experts=16 * 4 * t_roof)) == pytest.approx(25.0)
    assert read(SimpleNamespace(scopes=None)) is None
    assert read(_run(mla=1.0)) is None
