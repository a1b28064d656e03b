"""Readings that set a cell's limits, on the chip, in one process.

    python3 -m bench.calibrate --workload <cell> --seeds 1,2,3 --controls 3

For every seed: the program's checked warm-up rounds (through the harness's
own ``Program``) against the plain reference.  For the first ``--controls``
seeds also the reference computed with float8 matrix operands (the
lower-precision control) and the reference with half of every local batch
left out (a planted fault), each put in the program's place.  A state left
unchanged reads 1 on ``change_gap`` by the measure itself and needs no run.

Prints one JSON line per seed and variant, and writes them to
``chiprun_out/calibrate-<cell>.jsonl``.  The benchmark's own runs never run
this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax

from . import ROOT, compare, reference, spec
from .harness import Program, use_benchmark_cache


def _record(out, row):
    line = json.dumps(row)
    print(line, flush=True)
    out.write(line + "\n")
    out.flush()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--controls", type=int, default=3)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"bench.calibrate: needs a TPU, found {dev.platform}", file=sys.stderr)
        return 2
    use_benchmark_cache()
    cell = spec.load_cell(args.workload)
    rounds = int(cell.limits["rounds"])
    seeds = [int(s) for s in args.seeds.split(",")]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(ROOT, "chiprun_out", f"calibrate-{cell.name}.jsonl")
    B = cell.traffic["fl"]["local_batch"]
    prog = Program(cell)
    with open(path, "a") as out:
        for i, seed in enumerate(seeds):
            t0 = time.perf_counter()
            prog.start(seed)
            mine = prog.warm_up(seed, rounds)
            t_prog = time.perf_counter() - t0
            peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
            if i == 0:
                _record(out, {"cell": cell.name, "round_step_memory": prog.step_memory()})
            prog.free()
            variants = [("program", mine)]
            t0 = time.perf_counter()
            with jax.default_matmul_precision("highest"):
                ref = reference.reference_rounds(cell.shape, cell.traffic, seed, seed, rounds)
                t_ref = time.perf_counter() - t0
                if i < args.controls:
                    variants.append(("control_fp8", reference.reference_rounds(
                        cell.shape, cell.traffic, seed, seed, rounds,
                        quant=reference.fp8_quant)))
                    variants.append(("fault_half_batch", reference.reference_rounds(
                        cell.shape, cell.traffic, seed, seed, rounds, rows=B // 2)))
            leaves = compare.kept_leaves(ref.grad_norms)
            for name, got in variants:
                _record(out, {
                    "cell": cell.name, "seed": seed, "variant": name,
                    "numbers": compare.numbers(got, ref, []),
                    "losses": got.losses, "ref_losses": ref.losses,
                    "grad_norms": got.grad_norms, "ref_grad_norms": ref.grad_norms,
                    "change_norms": got.change_norms, "ref_change_norms": ref.change_norms,
                    "kept_leaves": leaves, "program_s": t_prog, "reference_s": t_ref,
                    "peak_bytes_in_use": peak})
    return 0


if __name__ == "__main__":
    sys.exit(main())
