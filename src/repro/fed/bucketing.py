"""jit-side helpers for the bucketed execution layout.

The bucketed round runs one local scan per bucket — ``[C_b, K_b, B]`` instead
of the padded ``[C, K_max, B]`` — and then *reassembles* the per-client
results into full ``[C]`` slot-order arrays before anything cross-client
happens.  That reassembly is the bitwise contract: every aggregation,
normalization and metric reduction sees exactly the array the padded layout
would have produced (per-client outputs are bitwise-equal because the
bucketed index streams and masks are prefixes of the padded ones, and masked
steps are exact no-ops), so the two layouts cannot drift.

``unbucket`` appends one zeros row to the bucket concatenation; unassigned
slots (invalid cohort padding) point at it via ``pos``, matching the exact
zeros the padded layout computes for fully-masked slots.

The fleet plane composes with this for free: a deterministic ``abort``
deadline caps each client's realized steps by its device tier
(``FleetModel.deadline_caps``), the pipeline folds those caps into the
bucket edges, and slow tiers land in narrow buckets — the scan never pays
for steps the deadline forbids (the tier <-> bucket mapping).

The robustness plane leans on the same reassembly contract: attacks,
robust aggregators and quarantine guards all consume the full slot-order
``[C]`` delta stack (never per-bucket slices), so coordinate medians,
trimmed means and Krum distances see identical operand order under both
layouts and ``padded == bucketed`` stays bitwise with the plane on.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from ..data.federated import BucketedBatch


def unbucket(parts, pos):
    """Concat per-bucket stacked pytrees ([C_b, ...] each) + a zeros row,
    then gather back to original [C, ...] slot order via ``pos``."""
    full = jax.tree.map(
        lambda *xs: jnp.concatenate(
            list(xs) + [jnp.zeros((1,) + xs[0].shape[1:], xs[0].dtype)], axis=0),
        *parts,
    )
    return jax.tree.map(lambda t: jnp.take(t, pos, axis=0), full)


def _take_slots(extra, slots):
    """A bucket's view of a full-[C] per-slot extra (array or pytree — e.g.
    the gathered per-client state, whose leaves are [C, ...])."""
    return jax.tree.map(lambda t: jnp.take(t, slots, axis=0), extra)


def scan_stack(fn: Callable) -> Callable:
    """``fn`` over a leading client axis by ``lax.scan``, outputs stacked:
    the sequential counterpart of ``jax.vmap(fn)`` (each client still uses
    the whole mesh)."""
    def run(*xs):
        _, ys = jax.lax.scan(lambda _, x: (None, fn(*x)), None, xs)
        return ys

    return run


def stage_cohort(run: Callable, batch, *per_slot):
    """``run(data, step_mask, *per_slot)`` over the cohort, in [C] slot order.

    ``run`` is ``jax.vmap(fn)`` or ``scan_stack(fn)``.  ``per_slot`` are
    full-[C] arrays or pytrees with [C, ...] leaves (the per-client step
    sizes, the gathered client-state rows).  A padded batch is one call; a
    bucketed one is one call per bucket on its slots' view of ``per_slot``,
    reassembled to [C] by :func:`unbucket`.
    """
    if not isinstance(batch, BucketedBatch):
        return run(batch.data, batch.step_mask, *per_slot)
    parts = [run(b.data, b.step_mask, *[_take_slots(a, b.slots) for a in per_slot])
             for b in batch.buckets]
    return unbucket(parts, batch.pos)
