"""Fixed-shape, jit-safe distribution summaries (in-round histograms).

FedShuffle's arguments are about *distributions* — per-client step counts
under imbalance, update norms, staleness under buffered aggregation, bytes
on the wire — but scalar round metrics (means, maxima) erase exactly that
structure.  This module computes fixed-size histograms *inside* the jitted
round from the existing slot-order ``[C]`` arrays, so surfacing a
distribution costs one ``searchsorted`` + ``segment_sum`` on device and one
small transfer, never a per-client host readback.

The cardinality contract: every histogram has a **static** bin count and
**static, config-derived edges** (python/numpy constants closed over at
trace time — never functions of runtime values or of the execution layout),
so telemetry can never cause a recompile and histograms from padded /
bucketed / legacy / engine rounds are directly comparable.  Out-of-range
values clamp into the first / last bin (the edge builders put ``+inf`` at
the top where the tail is unbounded).

Edge builders:

* :func:`pow2_edges` — ``[0, 1, 2, 4, ..., inf)`` for small-integer counts
  (local steps, staleness ticks): resolution where the mass is, one
  unbounded tail bin.
* :func:`log_edges` — log-uniform decades for positive scale-free values
  (update norms, wire bytes).

``fed.rounds`` emits (gated on ``fl.telemetry``, through
:func:`round_histograms`): ``hist_steps``, ``hist_update_norm``, plus
``hist_staleness`` when the fleet plane is on, ``hist_uplink_mbytes`` /
``hist_downlink_mbytes`` under a non-identity codec, ``hist_suspicion``
(update-norm / median-norm ratios) while the robustness plane is active, and
``hist_dp_scale`` under DP.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# histogram metric keys share this prefix — the train loop routes them to
# registry Histogram instruments instead of the scalar row
HIST_PREFIX = "hist_"


def pow2_edges(bins: int) -> np.ndarray:
    """``[0, 1, 2, 4, ..., 2**(bins-2), inf]`` — bins for count data."""
    if bins < 2:
        raise ValueError(f"need >= 2 bins, got {bins}")
    finite = [0.0, 1.0] + [float(2 ** k) for k in range(1, bins - 1)]
    return np.asarray(finite + [np.inf], np.float64)


def log_edges(lo: float, hi: float, bins: int) -> np.ndarray:
    """Log-uniform edges over ``[lo, hi]`` with clamped tails ([bins+1])."""
    if bins < 2:
        raise ValueError(f"need >= 2 bins, got {bins}")
    if not (0.0 < lo < hi):
        raise ValueError(f"need 0 < lo < hi, got ({lo}, {hi})")
    return np.logspace(np.log10(lo), np.log10(hi), bins + 1)


def fixed_histogram(values, edges, weights=None) -> jnp.ndarray:
    """Weighted histogram of ``values`` into static ``edges`` ([bins] f32).

    jit-safe: ``edges`` is a host constant, the output shape is static, and
    out-of-range values clamp into the boundary bins.  ``weights`` defaults
    to 1 per value (pass ``meta.valid`` to drop padding slots).
    """
    edges = np.asarray(edges, np.float64)
    bins = edges.size - 1
    v = jnp.ravel(jnp.asarray(values, jnp.float32))
    idx = jnp.clip(
        jnp.searchsorted(jnp.asarray(edges, jnp.float32), v, side="right") - 1,
        0, bins - 1)
    w = (jnp.ones_like(v) if weights is None
         else jnp.ravel(jnp.asarray(weights, jnp.float32)))
    return jax.ops.segment_sum(w, idx, num_segments=bins)


def slot_sqnorms(deltas) -> jnp.ndarray:
    """Per-slot squared L2 norms of a ``[C, ...]``-stacked update tree.

    Summed leaf-by-leaf in tree-leaf order, fp32 — the sequential driver's
    fused scan computes the identical expression per client, so the staged
    and fused paths report bitwise-equal norms.
    """
    leaves = jax.tree.leaves(deltas)
    return sum(
        jnp.sum(jnp.square(x.astype(jnp.float32)),
                axis=tuple(range(1, x.ndim)))
        for x in leaves)


def tree_sqnorm(tree) -> jnp.ndarray:
    """Scalar fp32 squared L2 norm, summed in tree-leaf order.

    The per-client form of :func:`slot_sqnorms` — the sequential driver's
    fused scan computes it per step so its reported norms match the staged
    paths' stacked computation.
    """
    return sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
               for x in jax.tree.leaves(tree))


def round_hist_edges(fl, *, with_staleness: bool, with_uplink: bool,
                     with_robust: bool = False, with_dp: bool = False,
                     with_downlink: bool = False) -> dict:
    """The static edge table for one configuration's round histograms.

    One definition shared by the jitted emitter (``fed.rounds``) and the
    host accumulator (``fed.train_loop`` pre-creates registry Histogram
    instruments from it), so device counts always merge into matching bins.
    """
    bins = fl.telemetry_bins
    edges = {
        "hist_steps": pow2_edges(bins),
        "hist_update_norm": log_edges(1e-9, 1e3, bins),
    }
    if with_staleness:
        edges["hist_staleness"] = pow2_edges(bins)
    if with_uplink:
        edges["hist_uplink_mbytes"] = log_edges(1e-6, 1e4, bins)
    if with_downlink:
        # the broadcast direction's per-slot wire cost (fed.comm downlink)
        edges["hist_downlink_mbytes"] = log_edges(1e-6, 1e4, bins)
    if with_robust:
        # per-client update-norm / cohort-median-norm ratio (fed.robust):
        # honest mass sits near 1, scaled attacks / diverged clients in the
        # upper tail — the round's suspicion profile at a glance
        edges["hist_suspicion"] = log_edges(1e-2, 1e3, bins)
    if with_dp:
        # per-client DP clip scale min(1, C/||delta||) (fed.privacy): mass
        # at the top edge = updates under the clip bound, the lower tail =
        # how hard the clip is biting — the round's clipping profile
        edges["hist_dp_scale"] = log_edges(1e-4, 1.0, bins)
    return edges


def round_histograms(edges: dict, valid, values: dict) -> dict:
    """The round's ``hist_*`` metrics: for every key of ``edges`` (the
    :func:`round_hist_edges` table, which decides what is emitted), the
    ``[C]`` array ``values[key]()`` binned into its static edges, weighted
    by ``valid``.  Values are thunks, so an absent key costs nothing."""
    return {k: fixed_histogram(values[k](), e, weights=valid)
            for k, e in edges.items()}
