"""Smoke run of the federated round on one TPU, in one process.

    python chip_smoke.py

Refuses to start unless JAX finds a TPU.  Then:

* Phase A — trains Qwen1.5-0.5B at its published widths for 3 rounds of the
  ``launch/train.py --arch qwen1.5-0.5b`` job through ``fed.train_loop.train``
  and checks: every round's ``local_loss`` finite, round 0 within 1.0 of
  ln(vocab) (random init), no compile after round 0.
* Phase B — runs each main-path Pallas kernel compiled for the chip against
  its jnp reference: RR index streams (exact), quantize pack/unpack on the
  embedding leaf (scales exact, values within one level), and a cohort-engine
  run with the Pallas RR backend (ServerState exact).  Each kernel's compiled
  HLO must hold a ``tpu_custom_call``, i.e. none ran in interpret mode.

Any failed check raises, so the exit code is non-zero.  The last line of
stdout is the JSON result; wall times printed on the way are smoke timings,
not benchmark numbers.
"""
from __future__ import annotations

import json
import math
import os
import sys
import time

import jax

ARCH = "qwen1.5-0.5b"
ROUNDS = 3


def expect(cond, what: str) -> None:
    if not cond:
        raise RuntimeError(f"check failed: {what}")


def tpu_device():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform {dev.platform!r}")
    return dev


def has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def phase_train(dev):
    from repro.configs.registry import get_arch
    from repro.launch.train import run_arch
    from repro.obs import sentinels

    snt = sentinels.sentinel()
    t0 = time.perf_counter()
    res = run_arch(ARCH, ROUNDS, smoke=False)
    wall = time.perf_counter() - t0

    cfg = get_arch(ARCH)
    params = res.state.params
    expect(params["embed"].shape == (cfg.vocab, cfg.d_model)
           and params["embed"].dtype == jax.numpy.dtype(cfg.dtype),
           f"published width: embed {params['embed'].shape} {params['embed'].dtype}")
    depth = {leaf.shape[0] for leaf in jax.tree.leaves(params["blocks"])}
    expect(depth == {cfg.n_layers}, f"published depth: {depth}")
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    print(f"phase A: {ARCH} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"vocab={cfg.vocab} dtype={cfg.dtype} params={n_params}")

    rows = res.metrics.rows
    expect(len(rows) == ROUNDS, f"{len(rows)} rounds logged")
    for row in rows:
        print(f"phase A round {row['round']}: local_loss={row['local_loss']!r} "
              f"jax_compiles={row['jax_compiles']}")
        expect(math.isfinite(row["local_loss"]), f"round {row['round']} loss finite")
    loss0, ref0 = rows[0]["local_loss"], math.log(cfg.vocab)
    expect(abs(loss0 - ref0) <= 1.0, f"round-0 loss {loss0} within 1.0 of ln(vocab)={ref0}")
    late = [row["jax_compiles"] for row in rows[1:]]
    expect(not any(late), f"compiles after round 0: {late}")
    peak = dev.memory_stats().get("peak_bytes_in_use")
    print(f"phase A: peak_bytes_in_use={peak} compiles_or_cache_loads={snt.count} "
          f"compile_or_load_secs={snt.secs!r} smoke_wall_secs={wall!r} "
          f"(smoke timing, not a benchmark)")
    # one full-width leaf for the quantize check; the rest of the state goes
    return params["embed"].astype(jax.numpy.float32)


def check_rr():
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.rr_perm.kernel import rr_indices_kernel
    from repro.kernels.rr_perm.ops import rr_indices
    from repro.kernels.rr_perm.ref import stream_key

    C, K, B = 16, 64, 4
    rng = np.random.default_rng(0)
    prekey = jnp.asarray(stream_key(0, np.arange(C, dtype=np.uint32), np.uint32(7), np))
    sizes = jnp.asarray(rng.integers(1, 4 * K * B, C), jnp.int32)
    spe = jnp.maximum(1, -(-sizes // B)).astype(jnp.int32)
    for mode in ("rr", "wr"):
        got = rr_indices(prekey, sizes, spe, B=B, K=K, mode=mode, backend="pallas")
        want = rr_indices(prekey, sizes, spe, B=B, K=K, mode=mode, backend="ref")
        expect(np.array_equal(np.asarray(got), np.asarray(want)),
               f"rr_indices pallas == ref ({mode})")
        compiled = rr_indices_kernel.lower(prekey, sizes, spe, B=B, K=K,
                                           mode=mode).compile()
        expect(has_kernel(compiled), f"rr kernel compiled for the chip ({mode})")
    print(f"phase B rr_perm: C={C} K={K} B={B} pallas == ref exactly (rr, wr)")


def check_quantize(leaf):
    import jax.numpy as jnp

    from repro.kernels.quantize.kernel import (quantize_pack_kernel,
                                               unpack_dequantize_kernel)
    from repro.kernels.quantize.ops import quantize_pack, unpack_dequantize
    from repro.kernels.rr_perm.ref import key_combine

    chunk, bits = 256, 4
    L = 2 ** (bits - 1) - 1
    v2 = jnp.asarray(leaf).reshape(-1, chunk)
    nc = v2.shape[0]
    keys = key_combine(jnp.uint32(12345), jnp.arange(nc, dtype=jnp.uint32), jnp)
    pk, sk = quantize_pack(v2, keys, bits=bits, backend="pallas")
    pr, sr = quantize_pack(v2, keys, bits=bits, backend="ref")
    expect(bool(jnp.array_equal(sk, sr)), "quantize scales pallas == ref")
    dk = unpack_dequantize(pk, sk, chunk=chunk, bits=bits, backend="pallas")
    dr = unpack_dequantize(pr, sr, chunk=chunk, bits=bits, backend="ref")
    level = sr[:, None] / L
    off = jnp.abs(dk - dr)
    mismatched = int(jnp.sum(dk != dr))
    worst = float(jnp.max(jnp.where(level > 0, off / jnp.where(level > 0, level, 1), off)))
    expect(worst <= 1.0 + 1e-5, f"dequantized within one level (worst {worst} levels)")
    for fn, args, kw in ((quantize_pack_kernel, (v2, keys), dict(bits=bits)),
                         (unpack_dequantize_kernel, (pk, sk), dict(chunk=chunk, bits=bits))):
        expect(has_kernel(fn.lower(*args, **kw).compile()),
               f"{fn.__name__} compiled for the chip")
    print(f"phase B quantize: leaf={tuple(leaf.shape)} chunks={nc} chunk={chunk} "
          f"bits={bits} scales equal; mismatched_levels={mismatched} of {v2.size} "
          f"(worst {worst!r} levels)")


def engine_round(backend: str, interpret: bool | None = None):
    """Phase B's cohort engine on the population quadratic task, whose sample
    rows follow the RR indices (so the kernel's output reaches the
    ServerState): ``(engine, jitted round step, initial state)``."""
    import jax.numpy as jnp

    from repro.configs.base import FLConfig
    from repro.data.federated import Population
    from repro.data.tasks import PopulationQuadraticTask
    from repro.fed.cohort import CohortEngine
    from repro.fed.losses import make_quadratic_loss
    from repro.fed.rounds import build_round_step, jit_round_step
    from repro.fed.strategy import bind_strategy, strategy_for

    task = PopulationQuadraticTask(dim=16, num_clients=64, samples_per_client=12)
    loss = make_quadratic_loss(task.dim)
    fl = FLConfig(num_clients=task.num_clients, cohort_size=8, sampling="uniform",
                  epochs=2, local_batch=4, algorithm="fedshuffle", local_lr=0.05,
                  server_lr=0.8, seed=11, engine="cohort")
    strat = bind_strategy(strategy_for(fl), fl, loss, num_clients=fl.num_clients)
    eng = CohortEngine.build(task, Population.build(fl, sizes=task.sizes()), fl,
                             rr_backend=backend, interpret=interpret)
    step = jit_round_step(build_round_step(loss, strat, fl, plane=eng.plane))
    state = strat.init({"x": jnp.linspace(-0.5, 0.5, task.dim, dtype=jnp.float32)})
    return eng, step, state


def check_engine():
    import jax.numpy as jnp
    import numpy as np

    states = {}
    for backend in ("device", "device_ref"):
        eng, step, state = engine_round(backend)
        lr = jnp.float32(1.0)
        with eng.round_plans(ROUNDS) as it:
            for r, plan in it:
                if r == 0 and backend == "device":
                    expect(has_kernel(step.lower(state, plan, lr).compile()),
                           "cohort round step holds the rr kernel")
                state, _ = step(state, plan, lr)
        states[backend] = jax.device_get(state)
    a, b = states["device"], states["device_ref"]
    expect(jax.tree.structure(a) == jax.tree.structure(b), "ServerState structure")
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        expect(np.array_equal(np.asarray(x), np.asarray(y)),
               "ServerState rr_backend device == device_ref")
    print(f"phase B cohort engine: {ROUNDS} rounds, ServerState device == device_ref "
          f"exactly (x[:4]={np.asarray(a.params['x'])[:4].tolist()})")


def main() -> None:
    dev = tpu_device()
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))
    from repro.launch.compile_cache import use_compile_cache

    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache: {use_compile_cache()}")
    leaf = phase_train(dev)
    check_rr()
    check_quantize(leaf)
    check_engine()
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
