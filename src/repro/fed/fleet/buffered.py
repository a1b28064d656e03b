"""Buffered-async server path: staleness weighting + the fleet state bank.

The FedBuff-style server (``fl.server_mode="buffered"``) aggregates each
tick's first-K arrivals through the *existing* strategy hooks: binding wraps
``agg_coeffs`` so every coefficient is multiplied by a staleness discount,
and ``aggregate`` (= ``weighted_sum(deltas, agg_coeffs(meta))``) inherits it
in both cohort modes.  The weighting contract:

    ``constant`` — w(tau) = 1            (pure FedBuff averaging)
    ``poly``     — w(tau) = (1 + tau) ** -fl.staleness_power

with tau the update's staleness in server ticks (``meta.staleness``; 0 for
work dispatched and aggregated in the same tick — and identically 0 in sync
mode, where the weight is exactly 1 and the math is untouched).

Per-client staleness counters ride ``ServerState.clients`` under the
reserved ``FLEET_STATE_KEY`` bank key, exactly like scaffold variates and
uplink error-feedback residuals: one row per client + a scratch row, rows
gathered/scattered O(cohort) inside the jitted round, untouched rows passed
through the local chain bit-for-bit.

Composition with the robustness plane (``repro.fed.robust``): staleness
discounts enter through the wrapped ``agg_coeffs``, and robust aggregators
consume exactly those coefficients — a stale adversary therefore carries
less weight in a weighted median / trimmed mean, and quarantine
renormalization (``renormalize_coeffs``) preserves the staleness-discounted
total mass, so buffered ticks keep the same scale contract as sync rounds.
"""
from __future__ import annotations

import jax.numpy as jnp

from ...configs.base import FLConfig

FLEET_STATE_KEY = "fleet"   # reserved ServerState.clients bank key


def fleet_client_state() -> dict:
    """One client's row of the fleet bank: cumulative arrival/staleness
    counters (fp32 scalars; the round driver increments the cohort's rows)."""
    return {"arrivals": jnp.zeros((), jnp.float32),
            "stale_sum": jnp.zeros((), jnp.float32)}


def slot_staleness(meta) -> jnp.ndarray:
    """The cohort's per-slot staleness as a [C] fp32 array.

    The single definition of the "no fleet fields => tau = 0" rule —
    hand-built test metas and sync-mode plans (``meta.staleness`` None or
    zeros) read as fresh everywhere staleness is consumed (the weighting
    below, the round driver's bank bookkeeping, the telemetry histograms)."""
    stal = getattr(meta, "staleness", None)
    if stal is None:
        return jnp.zeros_like(jnp.asarray(meta.valid, jnp.float32))
    return jnp.asarray(stal, jnp.float32)


def staleness_weights(fl: FLConfig, meta) -> jnp.ndarray:
    """Per-slot staleness discounts ([C] fp32, 1.0 at tau=0).

    Metas without fleet fields (hand-built test metas) weigh as tau=0."""
    stal = slot_staleness(meta)
    if fl.staleness == "constant":
        return jnp.ones_like(stal)
    return (1.0 + stal) ** jnp.float32(-fl.staleness_power)


def count_arrivals(cs: dict, meta) -> dict:
    """The cohort's fleet bank rows bumped for this round: one arrival each,
    and its staleness added.  Applied before the round's masked bank commit,
    so padding slots and dropped clients revert to what they read."""
    stal = slot_staleness(meta)
    fb = cs[FLEET_STATE_KEY]
    return {**cs, FLEET_STATE_KEY: {"arrivals": fb["arrivals"] + 1.0,
                                    "stale_sum": fb["stale_sum"] + stal}}


def fleet_metrics(meta, valid_sum) -> dict:
    """The fleet plane's round keys.  ``round_virtual_time`` is the slowest
    surviving client's wall time in a sync round, and the tick's span in a
    buffered one (its K-th arrival flushes it); ``valid_sum`` is the
    cohort's clamped valid count, the mean's denominator."""
    z = jnp.zeros_like(meta.valid)
    stal = slot_staleness(meta)
    arr = z if meta.arrive_time is None else jnp.asarray(meta.arrive_time, jnp.float32)
    drp = z if meta.dropped is None else jnp.asarray(meta.dropped, jnp.float32)
    return {"round_virtual_time": jnp.max(arr * meta.valid),
            "arrived_clients": meta.valid.sum(),
            "dropped_clients": drp.sum(),
            "mean_staleness": (stal * meta.valid).sum() / valid_sum}
