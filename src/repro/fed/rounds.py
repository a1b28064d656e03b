"""The federated round step: one jit-able driver over a bound FedStrategy.

``build_round_step(loss_fn, strategy, fl, num_clients, *, plane=None)``
returns ``round_step(state, batch, lr_mult) -> (state, metrics)``, a straight
line of stages:

1. materialise: an engine ``IndexPlan`` becomes a batch on device (``plane``);
2. broadcast: the cohort's rows of the client-state bank are gathered and,
   under a compressed downlink, each slot's round-start params rebuilt;
3. cohort: every client's local steps, ``vmapped`` or ``sequential``, over
   a padded or bucketed batch, give slot-order ``[C]`` deltas and states;
4. update path: DP clip -> attack -> uplink codec on that stack, then one
   combine: quarantine -> robust aggregator, secagg, or the weighted sum;
5. DP noise, the masked bank commit, the server update, the reject guard;
6. metrics: three base keys and one call per active plane.

Contracts.  Every cross-client step reads the same slot-order ``[C]`` stack
in every layout, so padded == bucketed and legacy == engine hold bitwise.
A sequential padded round with an empty update path (``stacked`` False)
instead folds ``coeff_i * delta_i`` into its client scan by
:func:`add_weighted`, the one accumulation rule, and never stacks the deltas.
A plane that is off adds no op and no metric key.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import FLConfig
from ..data.federated import Bucket, BucketedBatch, RoundBatch
from ..obs import hist as obs_hist
from ..obs import metrics_enabled, trace
from ..utils.pytree import tree_zeros_like
from .bucketing import scan_stack, stage_cohort
from .comm import (DOWNLINK_STATE_KEY, UPLINK_STATE_KEY, downlink_apply,
                   downlink_round_keys, mbytes_per_slot, round_keys,
                   uplink_apply, wire_metrics)
from .fleet import (FLEET_STATE_KEY, count_arrivals, fleet_active,
                    fleet_metrics, slot_staleness)
from .privacy import (add_dp_noise, dp_active, dp_clip_cohort, dp_metrics,
                      secagg_active, secagg_combine)
from .robust import (build_attack, guard_quarantines, guard_rejects,
                     params_ok, quarantine, robust_active, robust_metrics,
                     select_state, suspicion_ratio)
from .server import ServerState
from .strategy import (BoundStrategy, CohortState, FedStrategy, RoundCtx,
                       bind_strategy, weighted_sum)


def add_weighted(acc, delta, coeff_i):
    """``acc + coeff_i * delta`` in the accumulator's dtype: the sequential
    cohort's accumulation rule, shared by its fused scan and its combine."""
    with jax.named_scope("accumulate"):
        return jax.tree.map(
            lambda A, D: (A + coeff_i * D.astype(jnp.float32)).astype(A.dtype),
            acc, delta,
        )


def build_round_step(loss_fn: Callable,
                     strategy: "FedStrategy | BoundStrategy | None" = None,
                     fl: FLConfig | None = None, num_clients: int | None = None,
                     *, plane=None) -> Callable:
    if isinstance(strategy, FLConfig):
        raise TypeError("build_round_step takes the FLConfig as fl=...; its "
                        "second argument is the strategy (or None)")
    if not isinstance(strategy, BoundStrategy):
        if fl is None:
            raise TypeError("build_round_step needs an FLConfig (fl=...)")
        if num_clients is None:
            num_clients = fl.num_clients
    # a BoundStrategy passes through bind_strategy, which validates that any
    # fl/num_clients given here agree with the config it was bound over
    strat = bind_strategy(strategy, fl, loss_fn, num_clients=num_clients)
    fl, num_clients = strat.fl, strat.num_clients

    # What is on, read once.  A codec of None (hand-built strategies only)
    # is dense, like identity.
    codec, down = strat.codec, strat.down_codec
    up_on = codec is not None and codec.name != "identity"
    dl_on = down is not None and down.name != "identity"
    robust_on, dp_on, sa_on = robust_active(fl), dp_active(fl), secagg_active(fl)
    fleet_on = fleet_active(fl)
    g_quar = robust_on and guard_quarantines(fl)
    g_rej = robust_on and guard_rejects(fl)
    tele_hist = metrics_enabled(fl.telemetry)
    banked = strat.client_state is not None
    sequential = fl.cohort_mode == "sequential"
    # the update path runs on the slot-order [C] stack of the cohort's deltas
    stacked = up_on or robust_on or dp_on or sa_on
    apply_up = uplink_apply(codec) if up_on else None
    apply_down = downlink_apply(down) if dl_on else None
    attack = build_attack(fl) if robust_on else None
    hist_edges = obs_hist.round_hist_edges(
        fl, with_staleness=fleet_on, with_uplink=up_on, with_robust=robust_on,
        with_dp=dp_on, with_downlink=dl_on) if tele_hist else {}

    def materialize(batch):
        if isinstance(batch, (RoundBatch, BucketedBatch)):
            return batch
        # cohort-engine path: an IndexPlan / BucketedPlan gathered through
        # the resident bank (device RR backends regenerate the index streams
        # here, inside the jit)
        if plane is None:
            raise TypeError(
                "round_step received an index plan but build_round_step was "
                "called without plane=; pass the engine's DevicePlane")
        return plane.materialize(batch)

    def gather_bank(state, meta):
        """(bank row per slot, the cohort's gathered rows).  Invalid padding
        slots read, and later write, the scratch row, so a round's state
        traffic is O(cohort) whatever the population."""
        if not banked:
            return None, {}
        if state.clients is None:
            raise TypeError(
                f"round_step for local update {strat.local_update!r} / "
                f"uplink codec {codec.name if codec else None!r} got a "
                f"ServerState without a client state bank; build the "
                f"state with the bound strategy's init() (legacy "
                f"init_server predates stateful chains / error-feedback "
                f"codecs and keeps none).")
        with jax.named_scope("bank_gather"):
            ids = jnp.where(meta.valid > 0, meta.client_id,
                            num_clients).astype(jnp.int32)
            return ids, jax.tree.map(lambda b: jnp.take(b, ids, axis=0),
                                     state.clients)

    def broadcast(state, meta, cstate0):
        """The cohort state the clients start from.  Under a compressed
        downlink each slot's round-start params are rebuilt from its banked
        reference, vmapped over the slot-order stack before the cohort runs,
        and ride the "downlink" key: the client starts (and measures its
        update) from them, and the bank commit makes them the slot's next
        reference.  ``cstate0`` stays the gathered rows, what invalid slots
        revert to."""
        if not dl_on:
            return cstate0
        with jax.named_scope("downlink"):
            if down.seeded:
                dkeys = downlink_round_keys(fl.seed, meta.client_id, state.rnd, jnp)
            else:
                dkeys = jnp.zeros(meta.valid.shape, jnp.uint32)
            params_hat = jax.vmap(apply_down, in_axes=(None, 0, 0))(
                state.params, cstate0[DOWNLINK_STATE_KEY]["ref"], dkeys)
        return {**cstate0, DOWNLINK_STATE_KEY: {"ref": params_hat}}

    def fused_cohort(client, batch, eta, coeff, acc0, cs):
        """The sequential padded cohort with an empty update path: one scan
        that folds each client's weighted delta into ``acc0`` as it goes."""
        def body(acc, xs):
            data_i, mask_i, eta_i, coeff_i, cs_i = xs
            delta, loss, cs_new = client(data_i, mask_i, eta_i, cs_i)
            ys = (loss, cs_new)
            if tele_hist:
                ys = ys + (obs_hist.tree_sqnorm(delta),)
            return add_weighted(acc, delta, coeff_i), ys

        delta_agg, ys = jax.lax.scan(
            body, acc0, (batch.data, batch.step_mask, eta, coeff, cs))
        losses, new_cs, *slot_sq = ys
        return delta_agg, losses, new_cs, slot_sq[0] if tele_hist else None

    def update_path(deltas, new_cs, meta, rnd, keys):
        """DP clip -> attack -> uplink codec on the slot-order stack; the
        codec's error-feedback residuals commit into the cohort state."""
        info = {}
        with jax.named_scope("update_path"):
            if dp_on:
                # client side, the exact sensitivity bound; before attacks,
                # whose adversaries are not assumed to honour it
                deltas, info["dp_clipped"], info["dp_scale"] = dp_clip_cohort(deltas, fl)
            if attack is not None:
                # before encode: adversaries control their wire payload
                deltas = attack(deltas, meta, rnd)
            if up_on:
                deltas, ef = jax.vmap(apply_up)(
                    deltas, new_cs.get(UPLINK_STATE_KEY, {}), keys)
                if codec.client_init is not None:
                    new_cs = {**new_cs, UPLINK_STATE_KEY: ef}
        return deltas, new_cs, info

    def combine(deltas, coeff, acc0, meta, rnd):
        """Quarantine -> the robust aggregator, secagg, or the weighted sum
        (``strat.aggregate`` for a vmapped cohort, the ``add_weighted`` scan
        for a sequential one).  Returns (delta_agg, info)."""
        info = {}
        with jax.named_scope("update_path"):
            if g_quar:
                deltas, coeff, info = quarantine(
                    deltas, coeff, meta, with_suspicion="hist_suspicion" in hist_edges)
            elif "hist_suspicion" in hist_edges:
                info["suspicion"] = suspicion_ratio(deltas, meta)
            if sa_on:
                # the server sees only the masked modular sum; validation
                # pins aggregator="mean" and forbids quarantine here
                return secagg_combine(deltas, coeff, meta.valid, meta.dropped,
                                      meta.client_id, rnd, fl), info
            if robust_on:
                if strat.robust_aggregate is None:
                    return weighted_sum(deltas, coeff), info
                return strat.robust_aggregate(deltas, coeff, meta), info
        if not sequential:
            with jax.named_scope("accumulate"):
                return strat.aggregate(deltas, meta), info
        delta_agg, _ = jax.lax.scan(
            lambda acc, xs: (add_weighted(acc, *xs), None), acc0, (deltas, coeff))
        return delta_agg, info

    def commit_bank(state, meta, ids, cstate0, new_cs):
        """(CohortState, new bank): invalid slots commit exactly what they
        read, then every slot scatters back to its own row in slot order."""
        if not banked:
            return None, None
        if FLEET_STATE_KEY in new_cs:
            new_cs = count_arrivals(new_cs, meta)
        valid = meta.valid
        with jax.named_scope("bank_scatter"):
            upd = jax.tree.map(
                lambda n, o: jnp.where(
                    (valid > 0).reshape((-1,) + (1,) * (n.ndim - 1)), n, o),
                new_cs, cstate0)
            return CohortState(old=cstate0, new=upd), jax.tree.map(
                lambda b, u: b.at[ids].set(u.astype(b.dtype)), state.clients, upd)

    def round_step(state: ServerState, batch, lr_mult=1.0):
        batch = materialize(batch)
        meta = batch.meta
        prev_state = state      # what the reject guard reverts to
        with jax.named_scope("client_transform"):
            plan = strat.client_transform(meta, lr_mult)               # eta [C]
        momentum = state.opt.get("m", None)
        if momentum is None:
            momentum = tree_zeros_like(state.params)
        ids, cstate0 = gather_bank(state, meta)
        cstate_in = broadcast(state, meta, cstate0)

        def client(data_i, mask_i, eta_i, cs_i):
            p_i = cs_i[DOWNLINK_STATE_KEY]["ref"] if dl_on else state.params
            return strat.local_step(p_i, momentum, state.opt,
                                    data_i, mask_i, eta_i, cs_i)

        # per-client uplink stream keys (seed, client, round): the absolute
        # round counter makes a resumed run replay the same streams
        if up_on and codec.seeded:
            keys = round_keys(fl.seed, meta.client_id, state.rnd, jnp)
        else:
            keys = jnp.zeros(meta.valid.shape, jnp.uint32)
        with jax.named_scope("agg_coeffs"):
            coeff = strat.agg_coeffs(meta)                             # [C]
        acc0 = None
        if sequential:
            with jax.named_scope("accumulate"):
                acc0 = jax.tree.map(
                    lambda x: jnp.zeros_like(x, jnp.dtype(fl.accum_dtype)),
                    state.params)

        info = {}
        if sequential and not stacked and not isinstance(batch, BucketedBatch):
            delta_agg, losses, new_cs, slot_sq = fused_cohort(
                client, batch, plan.eta, coeff, acc0, cstate_in)
        else:
            run = scan_stack(client) if sequential else jax.vmap(client)
            deltas, losses, new_cs = stage_cohort(run, batch, plan.eta, cstate_in)
            deltas, new_cs, info = update_path(deltas, new_cs, meta, state.rnd, keys)
            slot_sq = obs_hist.slot_sqnorms(deltas) if tele_hist else None
            delta_agg, combine_info = combine(deltas, coeff, acc0, meta, state.rnd)
            info.update(combine_info)
        if sequential:
            with jax.named_scope("accumulate"):
                delta_agg = jax.tree.map(lambda a, p: a.astype(p.dtype), delta_agg,
                                         state.params)
        if dp_on:
            # counter-based per-(seed, round) Gaussian noise on the aggregate:
            # the same wherever and however the round is produced
            with jax.named_scope("update_path"):
                delta_agg, info["dp_sigma"] = add_dp_noise(
                    delta_agg, coeff, meta.valid, fl, state.rnd)

        cstate, new_clients = commit_bank(state, meta, ids, cstate0, new_cs)
        ctx = RoundCtx(batch=batch, lr_mult=lr_mult, momentum=momentum,
                       cstate=cstate)
        with jax.named_scope("server_update"):
            state = strat.server_update(state, delta_agg,
                                        jnp.asarray(fl.server_lr, jnp.float32), ctx)
        if new_clients is not None:
            # server opts build ServerState(params=, opt=, rnd=); the driver
            # owns the bank and re-attaches the scattered update
            state = state._replace(clients=new_clients)
        rejected = None
        if g_rej:
            # a blown round's updates are discarded in-jit; the round counter
            # still advances (skipped, not replayed)
            ok = params_ok(prev_state.params, state.params)
            state = select_state(ok, state, prev_state)
            rejected = 1.0 - ok.astype(jnp.float32)

        valid_sum = jnp.maximum(meta.valid.sum(), 1.0)
        metrics = {
            "local_loss": (losses * meta.valid).sum() / valid_sum,
            "delta_norm": jnp.sqrt(
                sum(jnp.sum(x.astype(jnp.float32) ** 2) for x in jax.tree.leaves(delta_agg))
            ),
            "cohort": meta.valid.sum(),
        }
        if up_on or dl_on:
            metrics.update(wire_metrics(codec if up_on else None,
                                        down if dl_on else None,
                                        state.params, meta.valid))
        if fleet_on:
            metrics.update(fleet_metrics(meta, valid_sum))
        if robust_on:
            metrics.update(robust_metrics(info, rejected))
        if dp_on:
            metrics.update(dp_metrics(info["dp_clipped"], info["dp_sigma"],
                                      meta.valid, valid_sum))
        if tele_hist:
            metrics.update(obs_hist.round_histograms(hist_edges, meta.valid, {
                "hist_steps": lambda: meta.num_steps,
                "hist_update_norm": lambda: jnp.sqrt(slot_sq),
                "hist_staleness": lambda: slot_staleness(meta),
                "hist_uplink_mbytes": lambda: mbytes_per_slot(codec, state.params, meta.valid),
                "hist_downlink_mbytes": lambda: mbytes_per_slot(down, state.params, meta.valid),
                "hist_suspicion": lambda: info["suspicion"],
                "hist_dp_scale": lambda: info["dp_scale"],
            }))
        return state, metrics

    # the train loop pre-creates its registry Histograms from the same
    # static edge table the jitted emitter closed over
    round_step.telemetry_hist_edges = hist_edges
    return round_step


def as_device_meta(meta):
    """ClientMeta -> device dtypes: float32 scalars, int64 ids -> int32.

    The single definition of the meta dtype policy — ``as_device_batch``
    (legacy path) and ``cohort.plan.as_device_plan`` (engine path) both use
    it, which is what keeps the two paths bitwise-interchangeable."""
    return type(meta)(*[
        None if a is None
        else jnp.asarray(a, jnp.float32 if a.dtype != jnp.int64 else jnp.int32)
        for a in meta])


def _device_nbytes(rb) -> int:
    """Bytes ``as_device_batch`` hands the device for ``rb``: four per meta
    scalar (float32 or int32 under the meta policy), every other array at
    its canonical JAX dtype."""
    meta = 4 * sum(a.size for a in rb.meta if a is not None)
    return meta + sum(x.size * jax.dtypes.canonicalize_dtype(x.dtype).itemsize
                      for x in jax.tree.leaves(rb._replace(meta=None)))


def local_steps(rb) -> tuple[int, int]:
    """(laid out, computed) local steps of a host RoundBatch / BucketedBatch:
    every slot of its ``[C, K]`` step masks, and what a sequential cohort
    computes of them, each client up to its last unmasked step."""
    masks = ([b.step_mask for b in rb.buckets] if isinstance(rb, BucketedBatch)
             else [rb.step_mask])
    computed = sum(
        int(np.max(np.where(m > 0, np.arange(1, m.shape[1] + 1), 0),
                   axis=1, initial=0).sum())
        for m in masks)
    return sum(m.size for m in masks), computed


def as_device_batch(rb):
    """Host RoundBatch / BucketedBatch (numpy) -> jnp pytree, float32 meta,
    inside a ``data/to_device`` span that carries :func:`_device_nbytes`;
    with a tracer active, a ``data/local_steps`` counter carries
    :func:`local_steps`."""
    if trace.active() is not None:
        laid_out, computed = local_steps(rb)
        trace.counter("data/local_steps", laid_out=laid_out, computed=computed)
    with trace.span("data/to_device", bytes=_device_nbytes(rb)):
        if isinstance(rb, BucketedBatch):
            return BucketedBatch(
                buckets=tuple(
                    Bucket(data=jax.tree.map(jnp.asarray, b.data), idx=None,
                           step_mask=jnp.asarray(b.step_mask),
                           slots=jnp.asarray(b.slots))
                    for b in rb.buckets),
                meta=as_device_meta(rb.meta),
                pos=jnp.asarray(rb.pos),
            )
        return type(rb)(
            data=jax.tree.map(jnp.asarray, rb.data),
            step_mask=jnp.asarray(rb.step_mask),
            meta=as_device_meta(rb.meta),
        )


_DONATION_SUPPORTED: bool | None = None


def _donation_supported() -> bool:
    """Probe (once) whether the default backend honors buffer donation.

    Older CPU jaxlibs ignore donation with a warning per compile; current
    ones alias in place silently — and in-place matters beyond politeness:
    a stateful local chain's ``[N+1, ...]`` client state bank is copied
    wholesale every round when the ``ServerState`` argument is not donated,
    turning the O(cohort) scatter into an O(N) memcpy.
    """
    global _DONATION_SUPPORTED
    if _DONATION_SUPPORTED is None:
        import warnings

        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            jax.jit(lambda x: x + 1, donate_argnums=(0,))(
                jnp.zeros((), jnp.float32))
        _DONATION_SUPPORTED = not any(
            "donat" in str(w.message).lower() for w in caught)
    return _DONATION_SUPPORTED


def jit_round_step(step: Callable, *, donate: bool | None = None) -> Callable:
    """jit a round step, donating the ``ServerState`` argument's buffers.

    Donation lets XLA update params/opt-state/client-state-bank in place
    instead of copying them every round — the caller must not reuse a state
    object after passing it (the train loop rebinds, so that holds).
    ``donate=None`` auto-disables only on backends that do not implement
    donation (probed once; those would warn every compile and copy anyway).
    """
    if donate is None:
        donate = _donation_supported()
    return jax.jit(step, donate_argnums=(0,) if donate else ())
