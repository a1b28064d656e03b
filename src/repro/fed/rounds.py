"""The federated round step — a thin jit-able driver over a FedStrategy.

``build_round_step(loss_fn, strategy, fl, num_clients)`` returns

    round_step(state: ServerState, batch: RoundBatch-as-jnp, lr_mult) ->
        (ServerState, metrics)

The driver owns ONLY cohort execution; everything algorithm-specific (local
step sizes, aggregation coefficients, server optimizer) comes from the bound
strategy hooks (``repro.fed.strategy``).  Two cohort execution modes:

* ``vmapped``    — clients of the cohort run in parallel (``jax.vmap``); on a
  mesh the client axis is sharded over (pod, data) and each client's local
  model replica occupies one model-parallel slice.  Cross-device FL layout.
* ``sequential`` — ``lax.scan`` over the cohort; each client uses the whole
  mesh (params FSDP+TP sharded) and the weighted delta is accumulated.
  Cross-silo / huge-model layout (deepseek-v3 class).  A client run alone
  computes its local steps only up to its last unmasked one (the local
  loop's trip count is traced; ``core.local.scan_to_last_step``).

Both modes compute *identical* math:
    Delta = sum_i coeff_i * (y_i - x),   coeff_i = valid_i * w~_i / q_i^S
    x    <- x + eta_g * Delta            (+ server optimizer state)
with per-client local steps  y <- y - (eta_l / c_i) * g  (masked RR scan).

When the bound strategy carries a non-identity uplink codec
(``FLConfig.uplink``; ``repro.fed.comm``), each client's Delta_i passes
through ``decode(encode(.))`` before aggregation — always vmapped over
stacked slot-order [C] arrays (the compressed sequential-padded round stages
its delta stack like the bucketed one), so codec float ops cannot be fused
differently across layouts and padded == bucketed stays bitwise, error-
feedback residuals and DIANA shifts (banked on ``ServerState.clients``
under "uplink") included.  ``identity`` is an exact pass-through: the
default path's op sequence is byte-for-byte the pre-uplink one.

When the strategy also carries a non-identity *downlink* codec
(``FLConfig.downlink``), the server's broadcast is compressed too: each
cohort slot's round-start params become ``ref_i + decode(encode(x - ref_i))``
against the client-held reference gathered from the bank (reserved key
"downlink"), computed ONCE, vmapped over the slot-order [C] stack *before*
the cohort executes — identical in every layout, so no extra staging is
needed.  The reconstruction is committed back as the slot's next reference
by the same masked O(cohort) scatter the other banks use (an unsampled
client's reference goes stale but never desyncs), and each client's shipped
update is measured from its own reconstruction (Q-NASTYA semantics).
``downlink="identity"`` (the default) skips all of it — broadcast, client
step and metric tree are byte-for-byte the pre-downlink ones.

When the byzantine-robustness plane is active (``FLConfig.attack`` /
``aggregator`` / ``guard``; ``repro.fed.robust``), the driver (1) lets the
configured attack rewrite the stacked slot-order deltas *before* codec
encode, (2) aggregates through the bound robust aggregator over explicit —
and, after a quarantine, renormalized — coefficients, and (3) may
where-select the previous ServerState when the reject guard trips.  The
sequential-padded round stages its delta stack like the compressed one, so
padded == bucketed stays bitwise; with the plane off (the default) none of
this traces — the op sequence is byte-for-byte the pre-robustness one.

When the privacy plane is active (``FLConfig.dp`` / ``secagg``;
``repro.fed.privacy``), the driver (1) L2-clips each client's *shipped*
update to ``dp_clip`` right after the local steps (before attacks and the
codec — client-side semantics, bitwise-equal to the ``"dp_clip"``
ClientTransform hook), (2) under ``secagg="pairwise"`` replaces the float
weighted sum with the masked modular fixed-point aggregation (the codec
roundtrip runs first: quantize-then-mask), and (3) under ``dp="on"`` adds
counter-based per-(seed, round) Gaussian noise to the aggregate before the
server update.  Off by default: the plane adds no ops and no metric keys —
bitwise-frozen like comm/fleet/obs/robust.

The step consumes either a materialized ``RoundBatch`` (legacy host
assembly) or, when built with ``plane=`` (a cohort-engine
:class:`~repro.fed.cohort.plane.DevicePlane`), an ``IndexPlan`` — indices
and scalars only — which the plane materializes *inside* the jit by
gathering the device-resident bank (and, for device RR backends,
regenerating the reshuffling streams statelessly on device).

Legacy call style ``build_round_step(loss_fn, fl, num_clients=...)`` still
works: the FLConfig's ``algorithm``/``server_opt`` strings resolve through
the strategy registry (see :func:`repro.fed.strategy.strategy_for`).
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
from .bucketing import scan_clients, vmap_clients
from .comm import (DOWNLINK_STATE_KEY, UPLINK_STATE_KEY, dense_bits,
                   downlink_apply, downlink_round_keys, mbytes_per_slot,
                   round_keys, uplink_apply, wire_bits_total)
from .fleet import FLEET_STATE_KEY, fleet_active, slot_staleness
from .privacy import (add_dp_noise, dp_active, dp_clip_cohort, secagg_active,
                      secagg_combine)
from .robust import (build_attack, guard_quarantines, guard_rejects,
                     params_ok, quarantine_masks, renormalize_coeffs,
                     robust_active, scrub_deltas, select_state,
                     suspicion_ratio)
from .server import ServerState
from .strategy import (BoundStrategy, CohortState, FedStrategy, RoundCtx,
                       bind_strategy, weighted_sum)


def build_round_step(loss_fn: Callable,
                     strategy: "FedStrategy | BoundStrategy | FLConfig | None" = None,
                     fl: FLConfig | None = None, num_clients: int | None = None,
                     *, plane=None) -> Callable:
    if isinstance(strategy, FLConfig):
        # legacy signature build_round_step(loss_fn, fl[, num_clients])
        if isinstance(fl, int) and num_clients is None:
            num_clients = fl
        elif fl is not None:
            raise TypeError("pass either (strategy, fl) or the legacy (fl, num_clients)")
        strategy, fl = None, strategy
    if not isinstance(strategy, BoundStrategy):
        if fl is None:
            raise TypeError("build_round_step needs an FLConfig (fl=...)")
        if num_clients is None:
            num_clients = fl.num_clients
    # a BoundStrategy passes through bind_strategy, which validates that any
    # fl/num_clients given here agree with the config it was bound over
    strat = bind_strategy(strategy, fl, loss_fn, num_clients=num_clients)
    fl, num_clients = strat.fl, strat.num_clients
    one_client = strat.local_step
    # the [N+1, ...] client state bank carries stateful local-chain state
    # AND the uplink codec's error-feedback residual (key "uplink")
    banked = strat.client_state is not None
    # uplink codec: clients encode their update in-jit, aggregation combines
    # the DECODED updates on slot-order [C] arrays (identical padded /
    # bucketed math); "identity" is an exact pass-through, so the default
    # config's float op sequence is unchanged
    codec = strat.codec
    apply_up = uplink_apply(codec) if codec is not None else None
    has_ef = codec is not None and codec.client_init is not None
    # downlink broadcast codec: with a non-identity fl.downlink the server
    # compresses the model delta against each slot's banked reference and the
    # client starts the round from its reconstruction; identity (or a
    # hand-built strategy, down_codec=None) broadcasts dense params — the
    # pre-downlink op sequence exactly
    down = strat.down_codec
    dl_on = down is not None and down.name != "identity"
    apply_down = downlink_apply(down) if dl_on else None
    # in-jit telemetry histograms (fl.telemetry): fixed-shape summaries over
    # the slot-order [C] arrays every path already stages, with static
    # config-derived edges (obs.hist cardinality contract).  "off" (the
    # default) adds no ops and no metric keys — bitwise-frozen.
    tele_hist = metrics_enabled(fl.telemetry)
    # byzantine-robustness plane (fed.robust): attacks rewrite the stacked
    # slot-order deltas BEFORE the uplink codec (adversaries control their
    # wire payload), robust aggregators / quarantine combine over explicit
    # renormalizable coefficients, and the reject guard where-selects the
    # previous state on post-update blowup.  All off by default: the plane
    # adds no ops and no metric keys — bitwise-frozen like comm/fleet/obs.
    robust_on = robust_active(fl)
    apply_attack = build_attack(fl) if robust_on else None
    g_quar = robust_on and guard_quarantines(fl)
    g_rej = robust_on and guard_rejects(fl)
    # privacy plane (fed.privacy): per-client DP clipping runs on the staged
    # slot-order stack right after the local steps (before attacks/codec —
    # client-side semantics), secagg replaces the float weighted sum with
    # the masked modular aggregation, DP noise lands on the aggregate.  Off
    # by default: no new ops, no new metric keys — bitwise-frozen.
    dp_on = dp_active(fl)
    sa_on = secagg_active(fl)
    hist_edges = obs_hist.round_hist_edges(
        fl, with_staleness=fleet_active(fl),
        with_uplink=codec is not None and codec.name != "identity",
        with_robust=robust_on, with_dp=dp_on, with_downlink=dl_on,
    ) if tele_hist else {}

    def round_step(state: ServerState, batch, lr_mult=1.0):
        if not isinstance(batch, (RoundBatch, BucketedBatch)):
            # cohort-engine path: an IndexPlan / BucketedPlan — materialize on
            # device (gather through the resident bank; device RR backends
            # also regenerate the index streams here, inside the jit)
            if plane is None:
                raise TypeError(
                    "round_step received an index plan but build_round_step was "
                    "called without plane=; pass the engine's DevicePlane")
            batch = plane.materialize(batch)
        bucketed = isinstance(batch, BucketedBatch)
        meta = batch.meta
        # the reject guard reverts to the round's input state — capture it
        # before anything rebinds ``state`` (safe under donation: reads of
        # the donated buffers happen inside this jit, before release)
        prev_state = state if g_rej else None
        with jax.named_scope("client_transform"):
            plan = strat.client_transform(meta, lr_mult)               # eta [C]
        momentum = state.opt.get("m", None)
        if momentum is None:
            momentum = tree_zeros_like(state.params)
        if banked:
            if state.clients is None:
                raise TypeError(
                    f"round_step for local update {strat.local_update!r} / "
                    f"uplink codec {codec.name if codec else None!r} got a "
                    f"ServerState without a client state bank; build the "
                    f"state with the bound strategy's init() (legacy "
                    f"init_server predates stateful chains / error-feedback "
                    f"codecs and keeps none).")
            # gather the cohort's rows of the per-client state bank (invalid
            # padding slots read — and later write — the scratch row, so a
            # round's state traffic is O(cohort) regardless of population)
            with jax.named_scope("bank_gather"):
                ids = jnp.where(meta.valid > 0, meta.client_id,
                                num_clients).astype(jnp.int32)
                cstate0 = jax.tree.map(lambda b: jnp.take(b, ids, axis=0),
                                       state.clients)
        else:
            cstate0 = {}

        # downlink broadcast: reconstruct each slot's round-start params from
        # its banked reference ONCE, vmapped over the slot-order [C] stack,
        # BEFORE the cohort executes — identical float ops in every layout.
        # The reconstruction rides the cohort state under the "downlink" key:
        # the untouched pass-through in one_client carries it to new_cs, and
        # the masked bank commit below makes it the slot's next reference.
        # cstate0 stays the GATHERED rows — invalid slots must revert to what
        # they read (every padding slot aims at the scratch row, and their
        # writes must agree), not to a per-slot reconstruction.
        cstate_in = cstate0
        if dl_on:
            with jax.named_scope("downlink"):
                if down.seeded:
                    dkeys = downlink_round_keys(fl.seed, meta.client_id,
                                                state.rnd, jnp)
                else:
                    dkeys = jnp.zeros(meta.valid.shape, jnp.uint32)
                params_hat = jax.vmap(apply_down, in_axes=(None, 0, 0))(
                    state.params, cstate0[DOWNLINK_STATE_KEY]["ref"], dkeys)
            cstate_in = {**cstate0, DOWNLINK_STATE_KEY: {"ref": params_hat}}

        def client(data_i, mask_i, eta_i, cs_i):
            # with the downlink compressed, the client's round-start point is
            # its own reconstruction (its update is measured from there too)
            p_i = cs_i[DOWNLINK_STATE_KEY]["ref"] if dl_on else state.params
            return one_client(p_i, momentum, state.opt,
                              data_i, mask_i, eta_i, cs_i)

        # per-client uplink stream keys (seed, client, round) — only codecs
        # with sampling randomness consume them; keyed off the absolute round
        # counter so a checkpoint resume replays identical streams
        if apply_up is not None and codec.seeded:
            keys = round_keys(fl.seed, meta.client_id, state.rnd, jnp)
        else:
            keys = jnp.zeros(meta.valid.shape, jnp.uint32)

        def uplink_cohort(deltas, new_cs):
            """Encode+decode the cohort's stacked slot-order deltas; commit
            new error-feedback residuals into the cohort state."""
            if apply_up is None:
                return deltas, new_cs
            dhat, ef2 = jax.vmap(apply_up)(
                deltas, new_cs.get(UPLINK_STATE_KEY, {}), keys)
            if has_ef:
                new_cs = {**new_cs, UPLINK_STATE_KEY: ef2}
            return dhat, new_cs

        def secagg_agg(deltas, coeff):
            """Masked modular fixed-point aggregation (fed.privacy.secagg):
            pairwise masks cancel exactly, dropped clients' shares recovered."""
            with jax.named_scope("update_path"):
                return secagg_combine(deltas, coeff, meta.valid, meta.dropped,
                                      meta.client_id, state.rnd, fl)

        def robust_combine(deltas):
            """Aggregate the decoded slot-order stack under the robustness
            plane: quarantine -> coefficient renormalization -> the bound
            robust aggregator (``mean`` == the canonical weighted_sum)."""
            coeff = strat.agg_coeffs(meta)                           # [C]
            info = {"quarantined_clients": jnp.float32(0.0),
                    "suspected_adversaries": jnp.float32(0.0)}
            if g_quar:
                healthy, suspected = quarantine_masks(deltas, meta)
                info["quarantined_clients"] = (meta.valid * (1.0 - healthy)).sum()
                info["suspected_adversaries"] = suspected.sum()
                coeff = renormalize_coeffs(coeff, healthy)
                if "hist_suspicion" in hist_edges:
                    info["suspicion"] = suspicion_ratio(deltas, meta)
                # zero the quarantined slots' values too: a zeroed
                # coefficient alone would still leak NaN/Inf through
                # sorted-scan estimators (0 * nan = nan)
                deltas = scrub_deltas(deltas, healthy)
            elif "hist_suspicion" in hist_edges:
                info["suspicion"] = suspicion_ratio(deltas, meta)
            combine = strat.robust_aggregate
            if sa_on:
                # robust plane limited to attack / reject here — validation
                # pins aggregator="mean" and forbids quarantine under secagg
                # (the server only ever sees the blinded sum)
                return secagg_agg(deltas, coeff), info
            if combine is None:       # hand-built strategy: canonical mean
                return weighted_sum(deltas, coeff), info
            return combine(deltas, coeff, meta), info

        rb_info = None
        slot_sq = None  # [C] squared update norms, only under telemetry
        dp_clipped = dp_scale = dp_sigma = None  # privacy-plane telemetry
        if fl.cohort_mode == "vmapped":
            if bucketed:
                # per-bucket [C_b, K_b] scans, reassembled to [C] slot order
                # before any cross-client math — bitwise-identical aggregate
                deltas, losses, new_cs = vmap_clients(client, batch, plan.eta,
                                                      cstate_in)
            else:
                deltas, losses, new_cs = jax.vmap(client)(
                    batch.data, batch.step_mask, plan.eta, cstate_in)
            with jax.named_scope("update_path"):
                if dp_on:
                    # client-side DP clipping of the shipped update (the exact
                    # sensitivity bound) — before attacks: adversaries are not
                    # assumed to honor it (that is the robust plane's problem)
                    deltas, dp_clipped, dp_scale = dp_clip_cohort(deltas, fl)
                if apply_attack is not None:
                    # before encode: adversaries control their wire payload
                    deltas = apply_attack(deltas, meta, state.rnd)
                deltas, new_cs = uplink_cohort(deltas, new_cs)
            if tele_hist:
                slot_sq = obs_hist.slot_sqnorms(deltas)
            if robust_on:
                with jax.named_scope("update_path"):
                    delta_agg, rb_info = robust_combine(deltas)
            elif sa_on:
                delta_agg = secagg_agg(deltas, strat.agg_coeffs(meta))
            else:
                with jax.named_scope("accumulate"):
                    delta_agg = strat.aggregate(deltas, meta)
        else:  # sequential: the scan accumulates coeff_i * Delta_i as it goes,
            # so the strategy contributes through agg_coeffs rather than the
            # whole-cohort aggregate hook
            with jax.named_scope("agg_coeffs"):
                coeff = strat.agg_coeffs(meta)                         # [C]
            acc_dt = jnp.dtype(fl.accum_dtype)
            with jax.named_scope("accumulate"):
                acc0 = jax.tree.map(lambda x: jnp.zeros_like(x, acc_dt), state.params)

            def add_weighted(acc, delta, coeff_i):
                # THE accumulation rule — one definition, shared by the fused
                # and the staged paths (the bitwise contract between them)
                with jax.named_scope("accumulate"):
                    return jax.tree.map(
                        lambda A, D: (A + coeff_i * D.astype(jnp.float32)).astype(A.dtype),
                        acc, delta,
                    )

            deltas = None
            if bucketed:
                # per-bucket client scans stage stacked deltas, then the same
                # coeff_i-weighted accumulation replays in slot order
                deltas, losses, new_cs = scan_clients(client, batch, plan.eta,
                                                      cstate_in)
            elif ((apply_up is not None and codec.name != "identity")
                  or robust_on or dp_on or sa_on):
                # compressed uplink / robustness / privacy planes: stage the
                # per-client deltas (scan) so the codec, attacks, robust
                # aggregators, DP clip and secagg masks run vmapped on the
                # stacked [C] slot-order arrays, like every other layout.
                # Applying them inside the fused scan body instead would let
                # XLA contract their float ops differently there (FMA
                # fusion), silently breaking the padded == bucketed bitwise
                # contract (error-feedback residuals, cross-client
                # estimators).
                def stage(_, xs):
                    return None, client(*xs)

                _, (deltas, losses, new_cs) = jax.lax.scan(
                    stage, None,
                    (batch.data, batch.step_mask, plan.eta, cstate_in))

            if deltas is not None:
                with jax.named_scope("update_path"):
                    if dp_on:
                        # same client-side clip as the vmapped path (slot order)
                        deltas, dp_clipped, dp_scale = dp_clip_cohort(deltas, fl)
                    if apply_attack is not None:
                        deltas = apply_attack(deltas, meta, state.rnd)
                    deltas, new_cs = uplink_cohort(deltas, new_cs)
                if tele_hist:
                    slot_sq = obs_hist.slot_sqnorms(deltas)

                if robust_on:
                    with jax.named_scope("update_path"):
                        delta_agg, rb_info = robust_combine(deltas)
                elif sa_on:
                    delta_agg = secagg_agg(deltas, coeff)
                else:
                    def accum(acc, xs):
                        delta, coeff_i = xs
                        return add_weighted(acc, delta, coeff_i), None

                    delta_agg, _ = jax.lax.scan(accum, acc0, (deltas, coeff))
            else:
                def body(acc, xs):
                    data_i, mask_i, eta_i, coeff_i, cs_i = xs
                    delta, loss, cs_new = client(data_i, mask_i, eta_i, cs_i)
                    ys = (loss, cs_new)
                    if tele_hist:
                        # telemetry extends the scan ys; the off path's body
                        # is literally the pre-telemetry one
                        ys = ys + (obs_hist.tree_sqnorm(delta),)
                    return add_weighted(acc, delta, coeff_i), ys

                delta_agg, ys = jax.lax.scan(
                    body, acc0,
                    (batch.data, batch.step_mask, plan.eta, coeff, cstate_in)
                )
                if tele_hist:
                    losses, new_cs, slot_sq = ys
                else:
                    losses, new_cs = ys
            with jax.named_scope("accumulate"):
                delta_agg = jax.tree.map(lambda a, p: a.astype(p.dtype), delta_agg,
                                         state.params)

        if dp_on:
            # counter-based per-(seed, round) Gaussian noise on the weighted
            # aggregate — identical wherever the round is produced (legacy /
            # engine / prefetch / resume), mode-independent by construction
            with jax.named_scope("update_path"):
                delta_agg, dp_sigma = add_dp_noise(
                    delta_agg, strat.agg_coeffs(meta), meta.valid, fl, state.rnd)

        cstate = None
        new_clients = None
        if banked and FLEET_STATE_KEY in new_cs:
            # buffered server bookkeeping: bump the cohort's arrival /
            # staleness counters BEFORE the masked commit below, so invalid
            # padding slots (and dropped clients) revert to what they read
            fb = new_cs[FLEET_STATE_KEY]
            stal = slot_staleness(meta)
            new_cs = {**new_cs, FLEET_STATE_KEY: {
                "arrivals": fb["arrivals"] + 1.0,
                "stale_sum": fb["stale_sum"] + stal,
            }}
        if banked:
            # invalid slots commit exactly what they read (layout-independent
            # — the bucketed reassembly's zeros row never reaches the bank),
            # then every slot scatters back to its own bank row in slot order
            valid = meta.valid
            with jax.named_scope("bank_scatter"):
                upd = jax.tree.map(
                    lambda n, o: jnp.where(
                        (valid > 0).reshape((-1,) + (1,) * (n.ndim - 1)), n, o),
                    new_cs, cstate0)
                cstate = CohortState(old=cstate0, new=upd)
                new_clients = jax.tree.map(
                    lambda b, u: b.at[ids].set(u.astype(b.dtype)),
                    state.clients, upd)

        ctx = RoundCtx(batch=batch, lr_mult=lr_mult, momentum=momentum,
                       cstate=cstate)
        with jax.named_scope("server_update"):
            state = strat.server_update(state, delta_agg,
                                        jnp.asarray(fl.server_lr, jnp.float32), ctx)
        if new_clients is not None:
            # server opts construct ServerState(params=, opt=, rnd=) — the
            # driver owns the bank and re-attaches the scattered update
            state = state._replace(clients=new_clients)

        rejected = None
        if g_rej:
            # divergence guard: a blown round's param/opt/bank updates are
            # discarded in-jit; the round counter still advances (a rejected
            # round is skipped, not replayed — schedules/keys stay aligned)
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
        up_on = codec is not None and codec.name != "identity"
        if up_on:
            # bytes-on-wire accounting (static per client — every update is
            # model-shaped); identity adds no keys so the default metric tree
            # stays frozen
            bits_pc = wire_bits_total(codec, state.params)
            metrics["uplink_mbytes"] = meta.valid.sum() * jnp.float32(
                bits_pc / 8e6)
            metrics["uplink_compression"] = jnp.float32(
                dense_bits(state.params) / bits_pc)
        if dl_on:
            # the broadcast's side of the ledger, same static accounting
            dbits_pc = wire_bits_total(down, state.params)
            metrics["downlink_mbytes"] = meta.valid.sum() * jnp.float32(
                dbits_pc / 8e6)
            metrics["downlink_compression"] = jnp.float32(
                dense_bits(state.params) / dbits_pc)
        if up_on or dl_on:
            # both directions of the wire in one number; an identity (or
            # absent) direction is charged its honest dense cost
            ub = bits_pc if up_on else dense_bits(state.params)
            db = dbits_pc if dl_on else dense_bits(state.params)
            metrics["total_comm_mbytes"] = meta.valid.sum() * jnp.float32(
                (ub + db) / 8e6)
        if fleet_active(fl):
            # fleet telemetry — keys exist only when the fleet plane is on,
            # so every pre-existing configuration's metric tree stays frozen.
            # round_virtual_time: sync = slowest surviving client's wall
            # time; buffered = the tick's span (the K-th arrival flushes it).
            z = jnp.zeros_like(meta.valid)
            stal = slot_staleness(meta)
            arr = z if meta.arrive_time is None else jnp.asarray(meta.arrive_time, jnp.float32)
            drp = z if meta.dropped is None else jnp.asarray(meta.dropped, jnp.float32)
            metrics["round_virtual_time"] = jnp.max(arr * meta.valid)
            metrics["arrived_clients"] = meta.valid.sum()
            metrics["dropped_clients"] = drp.sum()
            metrics["mean_staleness"] = (stal * meta.valid).sum() / valid_sum
        if robust_on:
            # robustness telemetry — keys exist only while the plane is on
            # (same metric-tree freeze as the fleet/uplink keys above); the
            # counts are 0 whenever the corresponding guard is not active
            metrics["quarantined_clients"] = rb_info["quarantined_clients"]
            metrics["suspected_adversaries"] = rb_info["suspected_adversaries"]
            metrics["rounds_rejected"] = (jnp.float32(0.0) if rejected is None
                                          else rejected)
        if dp_on:
            # privacy telemetry — keys exist only while DP is on (same
            # metric-tree freeze as the other planes); clipped_frac is the
            # exact indicator from the clip itself, not a post-hoc norm test
            metrics["dp_clipped_frac"] = (dp_clipped * meta.valid).sum() / valid_sum
            metrics["dp_sigma"] = dp_sigma
        if tele_hist:
            # fixed-shape distribution summaries (obs.hist): hist_*-prefixed
            # [bins] counts — the train loop routes them to registry
            # Histogram instruments rather than the scalar metric row
            metrics["hist_steps"] = obs_hist.fixed_histogram(
                meta.num_steps, hist_edges["hist_steps"], weights=meta.valid)
            metrics["hist_update_norm"] = obs_hist.fixed_histogram(
                jnp.sqrt(slot_sq), hist_edges["hist_update_norm"],
                weights=meta.valid)
            if "hist_staleness" in hist_edges:
                metrics["hist_staleness"] = obs_hist.fixed_histogram(
                    slot_staleness(meta), hist_edges["hist_staleness"],
                    weights=meta.valid)
            if "hist_uplink_mbytes" in hist_edges:
                metrics["hist_uplink_mbytes"] = obs_hist.fixed_histogram(
                    mbytes_per_slot(codec, state.params, meta.valid),
                    hist_edges["hist_uplink_mbytes"], weights=meta.valid)
            if "hist_downlink_mbytes" in hist_edges:
                metrics["hist_downlink_mbytes"] = obs_hist.fixed_histogram(
                    mbytes_per_slot(down, state.params, meta.valid),
                    hist_edges["hist_downlink_mbytes"], weights=meta.valid)
            if "hist_suspicion" in hist_edges:
                metrics["hist_suspicion"] = obs_hist.fixed_histogram(
                    rb_info["suspicion"], hist_edges["hist_suspicion"],
                    weights=meta.valid)
            if "hist_dp_scale" in hist_edges:
                metrics["hist_dp_scale"] = obs_hist.fixed_histogram(
                    dp_scale, hist_edges["hist_dp_scale"],
                    weights=meta.valid)
        return state, metrics

    # the host side (train loop) pre-creates matching registry Histograms
    # from the same static edge table the jitted emitter closed over
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
