import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")
)

"""Perf hillclimb driver (§Perf): lowers tagged optimization variants of the
three chosen (arch x shape) pairs and records the roofline terms per
iteration.  Each variant is an ArchConfig override set; dataflow is identical
to dryrun.run_one (same JSON artifacts, tagged).

  PYTHONPATH=src python -m repro.launch.hillclimb --pair hymba
"""
import argparse
import dataclasses

from ..configs.registry import get_arch
from ..utils.logging import log

# pair -> (arch, shape, [(tag, overrides, hypothesis)])
PAIRS = {
    # worst roofline fraction: memory term 5.7s, temp 2.1 TiB/dev at baseline
    "hymba": ("hymba-1.5b", "train_4k", [
        ("it1-banded", {"opt_banded_window": True},
         "windowed scores vs full T dominate bytes; banding cuts them ~Tk/band=3.2x"),
        ("it2-remat", {"opt_banded_window": True, "remat": "full"},
         "per-layer bwd residuals dominate temp; remat trades ~1.3x flops for >10x temp"),
        ("it3-xent", {"opt_banded_window": True, "remat": "full", "opt_onehot_xent": True},
         "fp32 logit gather all-gathers [B,S,V]; one-hot contraction stays sharded"),
    ]),
    # the paper's own regime at flagship scale: sequential FSDP federated round
    "qwen2": ("qwen2-72b", "train_4k", [
        ("it1-xent", {"opt_onehot_xent": True},
         "CE picked-logit gather over tp-sharded 152k vocab all-gathers fp32 logits"),
        ("it2-seqshard", {"opt_onehot_xent": True, "opt_seq_shard": True},
         "residual-stream all-reduces -> RS+AG at half volume (sequence parallel)"),
        ("it3-bf16acc", {"opt_onehot_xent": True, "__setup__": {"accum_dtype": "bfloat16"}},
         "the fp32 cohort delta accumulator doubles param-sized HBM traffic; bf16 halves it"),
        ("it4-vmapped", {"__setup__": {"cohort_mode": "vmapped"}},
         "cross-device layout: 16 parallel clients (1 per model-slice) instead of a "
         "4-client FSDP scan — fewer param all-gathers per round at higher residency"),
    ]),
    # most collective-bound baseline: 714ms collective vs 697ms memory
    "deepseek": ("deepseek-v3-671b", "prefill_32k", [
        ("it1-seqshard", {"opt_seq_shard": True},
         "per-layer activation all-reduce of [B,32k,7168] dominates; RS+AG halves it"),
        ("it5-seqinput", {"__setup__": {"seq_over_model": True}},
         "shard the 32k token dim over the model axis at the INPUT (not per-layer "
         "constraints): XLA propagates seq-sharding; attention gathers only locally"),
    ]),
}


def _resolve(arch_name: str, overrides: dict):
    cfg = get_arch(arch_name)
    ov = {k: v for k, v in overrides.items() if k != "__setup__"}
    return dataclasses.replace(cfg, **ov)


def main() -> None:
    from . import dryrun

    ap = argparse.ArgumentParser()
    ap.add_argument("--pair", required=True, choices=sorted(PAIRS))
    ap.add_argument("--iter", default=None, help="run only this tag")
    ap.add_argument("--unroll", action="store_true")
    ap.add_argument("--out", default="benchmarks/results/dryrun")
    args = ap.parse_args()

    arch, shape, iters = PAIRS[args.pair]
    for tag, overrides, hypothesis in iters:
        if args.iter and tag != args.iter:
            continue
        log(f"hillclimb {args.pair}/{tag}: {hypothesis}")
        cfg = _resolve(arch, overrides)

        # monkey-patch the registry entry for this lowering only
        import repro.configs.registry as registry

        orig = registry.ARCHS[arch]
        registry.ARCHS[arch] = cfg
        try:
            dryrun.run_one(arch, shape, multi_pod=False, out_dir=args.out,
                           tag=tag, unroll=args.unroll,
                           setup_kwargs=overrides.get("__setup__"))
        finally:
            registry.ARCHS[arch] = orig


if __name__ == "__main__":
    main()
