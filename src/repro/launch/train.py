"""Federated training launcher.

Three jobs:

* ``--arch <id>`` (no ``--smoke``): the arch at its published config
  (``get_arch``) on a small federated token job — 16 lognormal-size clients,
  seq_len 512, a uniform cohort of 4 in the sequential cohort layout.  This
  is the entry point on the TPU (``chip_smoke.py`` drives the same job).
* ``--arch <id> --smoke``: the same family ``.reduced()`` to <=2 layers and
  d_model <=128, seq_len 32 — proves the full stack end-to-end on CPU.
* ``--config charlm_e2e``: the paper's char-LM experiment.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b --smoke
  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b --rounds 3
  PYTHONPATH=src python -m repro.launch.train --config charlm_e2e --rounds 300
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from ..configs.base import ArchConfig, FLConfig
from ..configs.registry import get_arch
from ..data.federated import FederatedPipeline, Population
from ..data.tasks import CharLMTask, TokenTask
from ..fed.losses import make_loss
from ..fed.train_loop import TrainResult, train
from ..models.model import build_model
from ..utils.logging import log
from .compile_cache import use_compile_cache


def arch_job(arch: str, *, smoke: bool, algorithm: str = "fedshuffle",
             server_opt: str = "sgd", uplink: str = "identity"
             ) -> tuple[ArchConfig, FLConfig, TokenTask]:
    """(model config, FL config, token task) of the ``--arch`` job.

    ``smoke`` trains the reduced same-family config; otherwise the published
    config trains at full width — in the sequential cohort layout, since a
    vmapped cohort of full-width replicas does not fit one chip's HBM.
    """
    if smoke:
        cfg, seq_len = get_arch(arch).reduced(), 32
        fl = FLConfig(num_clients=6, cohort_size=3, sampling="uniform", epochs=1,
                      local_batch=2, algorithm=algorithm, local_lr=0.05,
                      server_opt=server_opt, mean_samples=4, seed=0, uplink=uplink)
    else:
        cfg, seq_len = get_arch(arch), 512
        fl = FLConfig(num_clients=16, cohort_size=4, sampling="uniform", epochs=1,
                      local_batch=2, algorithm=algorithm, local_lr=0.05,
                      server_opt=server_opt, imbalance="lognormal", mean_samples=4,
                      cohort_mode="sequential", telemetry="metrics", seed=0,
                      uplink=uplink)
    extras = {}
    if cfg.family == "vlm":
        extras["patches"] = (cfg.num_patches, cfg.d_model)
    if cfg.family == "audio":
        extras["frames"] = (cfg.src_frames, cfg.d_model)
    task = TokenTask(vocab=cfg.vocab, seq_len=seq_len, num_clients=fl.num_clients,
                     seed=fl.seed, extras=extras)
    return cfg, fl, task


def run_arch(arch: str, rounds: int, algorithm: str = "fedshuffle",
             server_opt: str = "sgd", uplink: str = "identity", *,
             smoke: bool) -> TrainResult:
    """Train the ``--arch`` job (see :func:`arch_job`) through the train loop."""
    cfg, fl, task = arch_job(arch, smoke=smoke, algorithm=algorithm,
                             server_opt=server_opt, uplink=uplink)
    pipe = FederatedPipeline(task, Population.build(fl), fl)
    model = build_model(cfg)
    res = train(make_loss(model), jax.jit(model.init)(jax.random.PRNGKey(0)),
                pipe, fl, rounds, name=f"{'smoke' if smoke else 'full'}-{arch}",
                log_every=max(1, rounds // 5))
    first, last = res.metrics.rows[0]["local_loss"], res.metrics.rows[-1]["local_loss"]
    log(f"{arch}: loss {first:.4f} -> {last:.4f}")
    return res


def run_charlm_e2e(rounds: int, algorithm: str, server_opt: str,
                   checkpoint: str | None, uplink: str = "identity") -> None:
    """The e2e driver: ~100M-param char-LM, heterogeneous clients."""
    from ..configs.paper_tasks import CHARLM_100M

    cfg = CHARLM_100M
    fl = FLConfig(num_clients=32, cohort_size=8, sampling="uniform", epochs=1,
                  local_batch=4, algorithm=algorithm, local_lr=0.05,
                  server_opt=server_opt, imbalance="lognormal", mean_samples=8,
                  cohort_mode="sequential", seed=1, uplink=uplink)
    task = CharLMTask(vocab=min(cfg.vocab, 512), seq_len=128, num_clients=fl.num_clients)
    import dataclasses
    cfg = dataclasses.replace(cfg, vocab=min(cfg.vocab, 512))
    pop = Population.build(fl)
    pipe = FederatedPipeline(task, pop, fl)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n = sum(int(x.size) for x in jax.tree.leaves(params))
    log(f"charlm e2e: {n/1e6:.1f}M params, {rounds} rounds")

    ev = task.batch(0, np.arange(4).reshape(1, 4))
    eval_batch = {k: jax.numpy.asarray(v[0]) for k, v in ev.items()}
    loss_fn = make_loss(model)
    eval_fn = jax.jit(lambda p: {"loss": loss_fn(p, eval_batch)[0]})
    res = train(loss_fn, params, pipe, fl, rounds, eval_fn=eval_fn, eval_every=20,
                schedule="staircase", checkpoint_path=checkpoint,
                checkpoint_every=100 if checkpoint else 0,
                name="charlm-e2e", log_every=10)
    print(res.metrics.csv())


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--config", default=None, choices=[None, "charlm_e2e"])
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--algorithm", default="fedshuffle")
    ap.add_argument("--server-opt", default="sgd")
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--uplink", default="identity",
                    help="uplink codec (repro.fed.comm.CODECS): identity | "
                         "qsgd | topk | randk | ef_qsgd | ef_randk")
    args = ap.parse_args()
    use_compile_cache()
    if args.config == "charlm_e2e":
        run_charlm_e2e(args.rounds, args.algorithm, args.server_opt,
                       args.checkpoint, args.uplink)
    else:
        run_arch(args.arch, args.rounds, args.algorithm, args.server_opt,
                 args.uplink, smoke=args.smoke)


if __name__ == "__main__":
    main()
