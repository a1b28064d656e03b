"""Self-healing round guards: quarantine bad clients, reject blown rounds.

Two in-jit defense layers that run *regardless* of which aggregator is
configured (defense in depth — a robust estimator bounds influence, a guard
removes obviously-corrupt inputs before it even votes):

* **Client quarantine** (``fl.guard`` in ``("quarantine", "full")``) — a
  per-client health check over the decoded slot-order delta stack: any
  NaN/Inf coordinate, or an update norm spiking past ``SPIKE_MULT`` x the
  cohort's median norm, zeroes that slot's effective valid mask for the
  aggregation and renormalizes the surviving coefficients so the total
  FedShuffle mass (hence the server step scale) is preserved.  Quarantine is
  per-round and aggregation-only: the client's loss still reports, its
  state-bank rows still commit, and it may return healthy next round.
* **Round rejection** (``fl.guard`` in ``("reject", "full")``) — a
  server-level divergence guard after ``server_update``: if the new
  parameters contain non-finite values or their norm blew past
  ``GROWTH_LIMIT`` x the pre-round norm, the round's param/opt/bank updates
  are discarded via an in-jit ``where``-select against the previous state
  (safe under buffer donation: the select happens inside the jit, before
  the donated inputs are released).  The round counter still advances, so
  round-indexed schedules, codec/attack key streams and resume validation
  stay aligned — a rejected round is a skipped round, not a replayed one.

Surfaced as ``quarantined_clients`` / ``suspected_adversaries`` /
``rounds_rejected`` metrics (and the ``hist_suspicion`` obs histogram) only
while the robust plane is active — the default metric tree stays frozen.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..server import ServerState
from .aggregators import _EPS, masked_median, slot_sqnorms

GUARDS = ("off", "quarantine", "reject", "full")

# norm-spike threshold: quarantine a client whose update norm exceeds this
# multiple of the cohort's median norm (median over valid finite slots)
SPIKE_MULT = 8.0
# divergence threshold: reject the round if ||params_new|| grows past this
# multiple of sqrt(||params_old||^2 + 1)  (the +1 absorbs near-zero starts)
GROWTH_LIMIT = 100.0


def guard_quarantines(fl) -> bool:
    return fl.guard in ("quarantine", "full")


def guard_rejects(fl) -> bool:
    return fl.guard in ("reject", "full")


def _finite_mask(deltas) -> jnp.ndarray:
    """[C] f32: 1 where every coordinate of a slot's update is finite."""
    bad = sum(
        jnp.sum((~jnp.isfinite(x.astype(jnp.float32))).astype(jnp.float32),
                axis=tuple(range(1, x.ndim)))
        for x in jax.tree.leaves(deltas))
    return (bad == 0).astype(jnp.float32)


def suspicion_ratio(deltas, meta) -> jnp.ndarray:
    """[C] update-norm / cohort-median-norm — the obs histogram's value.

    ~1 for honest clients; scaled attacks and diverged clients sit far in
    the tail.  Non-finite norms clamp to the top so they stay visible."""
    norm = jnp.sqrt(slot_sqnorms(deltas))
    fin = _finite_mask(deltas)
    med = masked_median(norm, meta.valid * fin)
    ratio = norm / jnp.maximum(med, _EPS)
    return jnp.where(jnp.isfinite(ratio), ratio, jnp.float32(1e9))


def quarantine_masks(deltas, meta):
    """(healthy [C], suspected [C]) over the decoded slot-order stack.

    ``suspected`` flags valid slots tripping the norm-spike heuristic (the
    "looks adversarial" signal); ``healthy`` additionally drops NaN/Inf
    slots — ``1 - healthy`` (on valid slots) is what quarantine removes.
    """
    norm = jnp.sqrt(slot_sqnorms(deltas))
    fin = _finite_mask(deltas)
    med = masked_median(norm, meta.valid * fin)
    spike = (norm > jnp.float32(SPIKE_MULT) * jnp.maximum(med, _EPS))
    spike = spike.astype(jnp.float32) * fin     # nonfinite handled separately
    suspected = meta.valid * spike
    healthy = fin * (1.0 - spike)
    return healthy, suspected


def scrub_deltas(deltas, healthy):
    """Zero quarantined slots' values in the stacked tree (``where``, not
    multiply — 0 * NaN is NaN, and a quarantined client's non-finite values
    must not leak through sorted-scan estimators downstream)."""
    return jax.tree.map(
        lambda d: jnp.where(
            healthy.reshape((-1,) + (1,) * (d.ndim - 1)) > 0,
            d, jnp.zeros((), d.dtype)),
        deltas)


def renormalize_coeffs(coeff, healthy) -> jnp.ndarray:
    """Zero quarantined coefficients, rescale survivors to the original
    total mass (sum is preserved, so the server step scale is unchanged;
    all-quarantined cohorts degrade to a zero aggregate / no-op round)."""
    cf = coeff.astype(jnp.float32)
    tot = cf.sum()
    kept = (cf * healthy).sum()
    scale = jnp.where(kept > 0, tot / jnp.where(kept > 0, kept, 1.0), 1.0)
    return cf * healthy * scale


def quarantine(deltas, coeff, meta, *, with_suspicion: bool):
    """The quarantine guard over a decoded slot-order stack.

    Returns ``(deltas, coeff, info)``: unhealthy slots' values zeroed, the
    coefficients renormalized over the healthy ones, and the round's
    ``quarantined_clients`` / ``suspected_adversaries`` counts (plus the
    ``suspicion`` ratios, measured before the scrub, on request)."""
    healthy, suspected = quarantine_masks(deltas, meta)
    info = {"quarantined_clients": (meta.valid * (1.0 - healthy)).sum(),
            "suspected_adversaries": suspected.sum()}
    coeff = renormalize_coeffs(coeff, healthy)
    if with_suspicion:
        info["suspicion"] = suspicion_ratio(deltas, meta)
    # zero the values too: a zeroed coefficient alone would still leak
    # NaN/Inf through sorted-scan estimators (0 * nan = nan)
    return scrub_deltas(deltas, healthy), coeff, info


def robust_metrics(info: dict, rejected) -> dict:
    """The robustness plane's round keys; a count reads 0 while its guard
    is off (``rejected`` is None without the reject guard)."""
    zero = jnp.float32(0.0)
    return {"quarantined_clients": info.get("quarantined_clients", zero),
            "suspected_adversaries": info.get("suspected_adversaries", zero),
            "rounds_rejected": zero if rejected is None else rejected}


def params_ok(prev_params, new_params) -> jnp.ndarray:
    """Scalar bool: the post-update parameters are finite and un-blown."""
    finite = jnp.array(True)
    for x in jax.tree.leaves(new_params):
        finite = finite & jnp.all(jnp.isfinite(x.astype(jnp.float32)))
    sq_new = sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                 for x in jax.tree.leaves(new_params))
    sq_prev = sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                  for x in jax.tree.leaves(prev_params))
    return finite & (sq_new <= jnp.float32(GROWTH_LIMIT**2) * (sq_prev + 1.0))


def select_state(ok, new: ServerState, prev: ServerState) -> ServerState:
    """In-jit keep/revert of a round's state updates (``rnd`` always
    advances — see the module docstring's skipped-not-replayed contract)."""

    def pick(n, p):
        return jax.tree.map(lambda a, b: jnp.where(ok, a, b), n, p)

    return ServerState(
        params=pick(new.params, prev.params),
        opt=pick(new.opt, prev.opt),
        rnd=new.rnd,
        clients=None if new.clients is None else pick(new.clients, prev.clients),
    )
