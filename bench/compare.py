"""The comparison that decides ``correct``: the program's first rounds against
the plain reference's, number by number, each against its limit.

* ``loss_gap`` — the worst round's |loss - reference loss| / reference loss.
* ``grad_gap`` — the first pseudo-gradient as the server optimizer gets it,
  read from the state after one round (x_1 - x_0): the worst leaf's gap of
  norms |‖p‖ - ‖r‖| / max(‖r‖, median leaf's ‖r‖).
* ``change_gap`` — the same measure of the change after the last warm-up
  round (x_R - x_0).
* ``input_mismatch`` — cohort slots, step masks and token entries where the
  rounds the program ran differ from the ones the reference derived.
* ``nonfinite_rounds`` — rounds of the window whose loss is not finite.

Leaves whose reference pseudo-gradient is under a thousandth of the median
leaf's move by round-off alone (the norm scales, held in bfloat16, never
move); they are left out of both norm gaps by that rule.
"""
from __future__ import annotations

import math

import numpy as np

KEPT = 1e-3


def kept_leaves(ref_grad: dict) -> list:
    med = float(np.median(list(ref_grad.values())))
    return sorted(k for k, v in ref_grad.items() if v >= KEPT * med)


def worst(xs) -> float:
    """The largest of ``xs``; NaN when any is NaN (``max`` would skip it)."""
    xs = [float(x) for x in xs]
    return math.nan if any(math.isnan(x) for x in xs) else max(xs)


def norm_gap(prog: dict, ref: dict, leaves: list) -> float:
    med = float(np.median([ref[k] for k in ref]))
    return worst(abs(prog[k] - ref[k]) / max(ref[k], med) for k in leaves)


def _slots(rnd, k_max: int):
    """A round's inputs as (client ids [C], step mask [C, K], tokens [C, K, B, T+1])
    from the reference's ``[(client, tokens [K_i, B, T+1])]``."""
    if not isinstance(rnd, list):
        return rnd
    ids = np.array([c for c, _ in rnd])
    mask = np.zeros((len(rnd), k_max), np.float32)
    toks = np.zeros((len(rnd), k_max) + rnd[0][1].shape[1:], np.int32)
    for slot, (_, t) in enumerate(rnd):
        mask[slot, :len(t)] = 1.0
        toks[slot, :len(t)] = t
    return ids, mask, toks


def input_mismatch(prog_inputs: list, ref_inputs: list) -> int:
    """Entries that differ between the rounds the program ran and the ones
    the reference derived: cohort slots, step masks, and tokens at unmasked
    steps.  Either side may be in the reference's per-client format."""
    bad = 0
    for got, want in zip(prog_inputs, ref_inputs, strict=True):
        k_max = max([len(t) for _, t in want] + [len(t) for _, t in got]
                    if isinstance(got, list) else [len(t) for _, t in want] + [got[1].shape[1]])
        ids, mask, toks = _slots(got, k_max)
        wids, wmask, wtoks = _slots(want, k_max)
        if ids.shape != wids.shape or toks.shape != wtoks.shape:
            return int(wtoks.size)
        bad += int(np.sum(ids != wids)) + int(np.sum(mask != wmask))
        bad += int(np.sum((toks != wtoks) & (wmask[:, :, None, None] > 0)))
    return bad


def numbers(prog, ref, window_losses: list) -> dict:
    """Every number compared; ``prog``/``ref`` are
    :class:`bench.reference.Readings`."""
    leaves = kept_leaves(ref.grad_norms)
    return {
        "loss_gap": worst(abs(p - r) / abs(r)
                          for p, r in zip(prog.losses, ref.losses, strict=True)),
        "grad_gap": norm_gap(prog.grad_norms, ref.grad_norms, leaves),
        "change_gap": norm_gap(prog.change_norms, ref.change_norms, leaves),
        "input_mismatch": input_mismatch(prog.inputs, ref.inputs),
        "nonfinite_rounds": sum(1 for x in window_losses if not math.isfinite(x)),
    }


def checks(values: dict, limits: dict) -> dict:
    """``{number: {"value": v, "limit": l}}``."""
    return {k: {"value": v, "limit": limits[k]} for k, v in values.items()}


def all_within(result: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in result.values())
