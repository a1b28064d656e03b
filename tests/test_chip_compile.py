"""Compiles for a described TPU v5e chip — the chip's own compiler, no chip.

The main-path Pallas kernels (interpret=False) and one full-width
Qwen1.5-0.5B round step at the ``chip_smoke.py`` job shapes are compiled for
one device of a described ``v5e:2x2`` topology.  The compiler refuses
block layouts the chip cannot tile and programs that exceed its HBM, which
interpret-mode tests never see.  Nothing runs, so nothing here says anything
about results or times.

The topology is described inside a module fixture only: one process at a
time may load the TPU library, so no import-time call may reach it.
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no compiler logs in /tmp
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("mode", ["rr", "wr"])
def test_rr_indices_kernel_compiles(one_chip, mode):
    from repro.kernels.rr_perm.kernel import rr_indices_kernel

    C = 16
    args = (_on(one_chip, (C,), jnp.uint32), _on(one_chip, (C,), jnp.int32),
            _on(one_chip, (C,), jnp.int32))
    _assert_kernel(rr_indices_kernel.lower(*args, B=4, K=64, mode=mode,
                                           interpret=False).compile())


# the tied Qwen1.5-0.5B embedding, flattened into chunk-256 rows
QWEN_EMBED_CHUNKS = 151936 * 1024 // 256


@pytest.mark.parametrize("bits", [2, 4, 8])
def test_quantize_kernels_compile(one_chip, bits):
    from repro.kernels.quantize.kernel import (quantize_pack_kernel,
                                               unpack_dequantize_kernel)

    nc, chunk = QWEN_EMBED_CHUNKS, 256
    pb = chunk * bits // 8
    _assert_kernel(quantize_pack_kernel.lower(
        _on(one_chip, (nc, chunk), jnp.float32), _on(one_chip, (nc,), jnp.uint32),
        bits=bits, interpret=False).compile())
    _assert_kernel(unpack_dequantize_kernel.lower(
        _on(one_chip, (nc, pb), jnp.uint8), _on(one_chip, (nc,), jnp.float32),
        chunk=chunk, bits=bits, interpret=False).compile())


def test_flash_attention_compiles(one_chip):
    from repro.kernels.flash_attention.kernel import flash_attention

    q = _on(one_chip, (1, 16, 2048, 64), jnp.bfloat16)
    _assert_kernel(flash_attention.lower(q, q, q, interpret=False).compile())


def test_cohort_engine_round_holds_rr_kernel(one_chip):
    """chip_smoke.py's phase-B round step with ``rr_backend="device"`` keeps
    the rr kernel: its indices must reach the ServerState, or XLA drops the
    kernel as dead code and the device == device_ref check proves nothing."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(os.path.dirname(__file__), "..", "chip_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)

    eng, step, state = smoke.engine_round("device", interpret=False)
    place = lambda tree: jax.tree.map(lambda x: _on(one_chip, x.shape, x.dtype), tree)
    _assert_kernel(step.lower(place(state), place(eng.device_plan(0)),
                              _on(one_chip, (), jnp.float32)).compile())


def test_qwen_round_step_fits_one_chip(one_chip):
    """The chip_smoke.py job's round step (sequential cohort layout) at
    Qwen1.5-0.5B's published widths: the compiler raises if it exceeds HBM."""
    from repro.data.federated import FederatedPipeline, Population
    from repro.fed.losses import make_loss
    from repro.fed.rounds import as_device_batch, build_round_step, jit_round_step
    from repro.fed.strategy import bind_strategy
    from repro.launch.train import arch_job
    from repro.models.model import build_model

    cfg, fl, task = arch_job("qwen1.5-0.5b", smoke=False)
    assert (cfg.n_layers, cfg.d_model, cfg.vocab, cfg.dtype) == (24, 1024, 151936, "bfloat16")
    assert fl.cohort_mode == "sequential"
    model = build_model(cfg)
    loss_fn = make_loss(model)
    strat = bind_strategy(None, fl, loss_fn, num_clients=fl.num_clients)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    state = jax.eval_shape(strat.init, params)
    batch = as_device_batch(
        FederatedPipeline(task, Population.build(fl), fl).round_batch(0))
    place = lambda tree: jax.tree.map(lambda x: _on(one_chip, x.shape, x.dtype), tree)
    step = jit_round_step(build_round_step(loss_fn, strat, fl, num_clients=fl.num_clients))
    compiled = step.lower(place(state), place(batch),
                          _on(one_chip, (), jnp.float32)).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < used < V5E_HBM_BYTES


def test_dsv2lite_round_step_fits_one_chip(one_chip, monkeypatch):
    """The benchmark's ``dsv2lite-xdev-equal`` round step (DeepSeek-V2-Lite
    at its published widths, one chip's share: 1 dense + 8 MoE layers, 8 of
    64 experts, 12,800 vocabulary rows; sequential cohort of 4, batch 2 x
    512) compiles for the chip within the 15.75 GiB XLA may use of the 16 GiB
    HBM, its routed experts through the grouped-matmul kernels, each under the
    ``experts`` scope the benchmark reads.  The process's backend is the CPU,
    so the model is told it lowers for the TPU (no interpret mode)."""
    from bench import clients, harness, spec
    from bench.weights import init_params
    from repro.data.federated import FederatedPipeline
    from repro.fed.rounds import as_device_batch

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cell = spec.load_cell("dsv2lite-xdev-equal")
    prog = harness.Program(cell)
    s = cell.shape
    state = jax.eval_shape(prog.strat.init, jax.eval_shape(lambda: init_params(s, 1)))
    task = clients.TokenRows(s.vocab, cell.traffic["seq_len"], 1)
    batch = as_device_batch(FederatedPipeline(task, prog.population, prog.fl).round_batch(0))
    place = lambda tree: jax.tree.map(lambda x: _on(one_chip, x.shape, x.dtype), tree)
    compiled = prog.step.lower(place(state), place(batch),
                               _on(one_chip, (), jnp.float32)).compile()
    kernels = [line for line in compiled.as_text().splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 9 and all("/moe/experts/" in k for k in kernels)
    assert 0 < compiled.memory_analysis().peak_memory_in_bytes < 15.75 * 1024**3


def test_expert_layer_moves_rows_by_gathers_only(one_chip, monkeypatch):
    """One ``moe_forward`` layer, forward and backward, at the
    ``dsv2lite-xdev-equal`` shapes (1024 tokens of width 2048, 8 of 64
    experts of 1408, top-6, 2 shared): dispatch and combine and their
    transposes move rows by gathers, so no scatter writes rows of width
    d_model (XLA's own transpose of a row gather is a sorted scatter-add)."""
    import re

    from repro.configs.base import ArchConfig, MoEConfig
    from repro.models.moe import moe_forward, moe_init

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    D = 2048
    cfg = ArchConfig(name="dsv2lite-moe-layer", family="moe", d_model=D, dtype="bfloat16",
                     moe=MoEConfig(num_experts=64, top_k=6, num_shared=2, expert_ff=1408,
                                   held=8))
    params = jax.eval_shape(lambda: moe_init(jax.random.PRNGKey(0), cfg, jnp.bfloat16))

    def loss(p, x):
        y, aux, _ = moe_forward(p, cfg, x)
        return jnp.sum(y.astype(jnp.float32)) + aux

    place = lambda tree: jax.tree.map(lambda a: _on(one_chip, a.shape, a.dtype), tree)
    hlo = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        place(params), _on(one_chip, (2, 512, D), jnp.bfloat16)).compile().as_text()
    scatters = re.findall(r"= \w+\[([\d,]*)\]\S* scatter\(", hlo)
    assert [s for s in scatters if s.split(",")[-1] == str(D)] == []
    assert re.search(rf"= bf16\[6144,{D}\]\S* gather\(", hlo)
