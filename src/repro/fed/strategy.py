"""Composable federated strategies — the paper's Algorithm 4 as an API.

A :class:`FedStrategy` declares the round recipe as a composition of four
orthogonal pieces instead of string branches scattered through the engine:

* a **(c, w~, q) parametrization** (:class:`~repro.core.algorithms.GenSpec`)
  choosing the local step-size normalization, the aggregation weighting and
  the aggregation normalization — the registries in ``repro.core.algorithms``;
* a **server optimizer** from :data:`SERVER_OPTS` (``sgd`` / ``momentum`` /
  ``mvr`` exact + App. F approx / ``adam``) — declared via :func:`chain` of
  pseudo-update transforms or as a bespoke whole-state update;
* a **local update rule** from :data:`LOCAL_UPDATES` — a declared
  :class:`~repro.core.local.ClientChain` of per-step client transforms
  (plain RR-SGD is the empty chain; the MVR-corrected steps of eq. 12-13,
  SCAFFOLD control variates, FedProx, per-step clipping are links).
  Transforms may keep persistent per-client state, banked ``[N+1, ...]`` on
  ``ServerState.clients`` and gathered/scattered O(cohort) per round.
  Resolution order: strategy pin, then ``FLConfig.local_update``, then the
  server optimizer's paired default; binding validates that every opt-state
  key the chain ``needs`` is ``provide``-d by the server opt;
* optionally an **equalized-step pipeline mode** (``fedavg_min`` /
  ``fedavg_mean``), which the data pipeline must apply — binding such a
  strategy against a config that would not equalize raises instead of
  silently running plain FedAvg.

:func:`bind_strategy` closes a strategy over a concrete ``FLConfig`` +
``loss_fn`` and yields the pure pytree hooks the round driver
(``repro.fed.rounds``) calls:

    ``init(params) -> ServerState``
    ``client_transform(meta, lr_mult) -> ClientPlan``      (per-client lr)
    ``agg_coeffs(meta) -> [C]`` / ``aggregate(deltas, meta) -> delta_agg``
    ``server_update(state, delta_agg, lr, ctx) -> ServerState``

Aggregation contract: ``agg_coeffs`` is the primitive — the ``sequential``
driver streams ``sum_i coeff_i * Delta_i`` through its scan, while the
``vmapped`` driver calls ``aggregate`` on the stacked deltas.  The built-in
``aggregate`` is exactly ``weighted_sum(deltas, agg_coeffs(meta))``; a
hand-built BoundStrategy replacing it with anything non-linear holds only in
``vmapped`` mode.

The driver owns only cohort execution (vmap vs lax.scan); everything
algorithm-specific lives here.  All preset compositions are bit-for-bit
identical to the original monolithic implementation.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from ..configs.base import FLConfig
from ..core import algorithms as _alg
from ..core.algorithms import GenSpec, PRESETS, agg_coeff, lr_scale
from ..core.local import (ClientChain, build_local_step, chain_client_template,
                          full_local_gradient, resolve_chain)
from ..data.federated import BucketedBatch
from ..obs import validate_telemetry_config
from ..utils.pytree import tree_copy, tree_zeros_like
from .bucketing import scan_clients, vmap_clients
from .comm import DOWNLINK_STATE_KEY, UPLINK_STATE_KEY, build_codec
from .fleet import (FLEET_STATE_KEY, fleet_active, fleet_client_state,
                    staleness_weights, validate_fleet_config)
from .privacy import privacy_active, validate_privacy_config
from .robust import (build_robust_aggregate, robust_active,
                     validate_robust_config)
from .server import ServerState

StrategyState = dict  # the server-side optimizer state (the ``opt`` dict)


class CohortState(NamedTuple):
    """The cohort's slice of the per-client state bank, in [C] slot order.

    ``old`` are the rows gathered at round start, ``new`` the finalized rows
    about to be scattered back (invalid padding slots carry ``old`` — i.e.
    ``new - old`` is exactly zero there), keyed like ``ServerState.clients``
    ({transform name: pytree with [C, ...] leaves}).  Server transforms use
    it to fold cohort state deltas into server state (e.g. SCAFFOLD's c).
    """

    old: Any
    new: Any


class RoundCtx(NamedTuple):
    """Traced round inputs a server update may need beyond the delta.

    ``batch`` is the device RoundBatch (data / step_mask / meta), ``lr_mult``
    the schedule multiplier, and ``momentum`` the momentum tree the clients
    used this round (zeros when the optimizer keeps none).  ``cstate`` is the
    cohort's :class:`CohortState` when the local chain keeps persistent
    per-client state (None otherwise).  A ``None`` ctx (legacy
    :func:`repro.fed.server.apply_server` path) applies only the parameter
    step of the optimizer.
    """

    batch: Any
    lr_mult: Any
    momentum: Any
    cstate: Any = None


class ClientPlan(NamedTuple):
    """Per-client local-work plan: the step sizes eta_l * lr_mult / c_i ([C]).
    (Which local-update *chain* runs is a static choice — see
    ``BoundStrategy.local_update`` / ``local_step``.)"""

    eta: jnp.ndarray


# ---------------------------------------------------------------------------
# Local update registry — name -> ClientChain (a declared composition of
# client transforms; see ``repro.core.local``) or, legacy, a raw factory
# make(loss_fn, fl) -> one_client(params, momentum, data, mask, eta).
# ---------------------------------------------------------------------------

LOCAL_UPDATES: dict[str, "ClientChain | Callable"] = {
    "sgd": ClientChain("sgd", ()),
    "mvr": ClientChain("mvr", ("mvr",)),
    # the new stateful / composed recipes
    "scaffold": ClientChain("scaffold", ("scaffold",)),
    "fedprox": ClientChain("fedprox", ("prox",)),
    "local_clip": ClientChain("local_clip", ("clip",)),
}


def register_local_update(name: str, make: "ClientChain | Callable", *,
                          overwrite: bool = False) -> None:
    """Register a local-update rule: a :class:`~repro.core.local.ClientChain`
    (preferred — composable, may declare per-client state) or the legacy raw
    factory ``make(loss_fn, fl) -> one_client(params, momentum, data, mask,
    eta) -> (delta, loss)``."""
    if not overwrite and name in LOCAL_UPDATES:
        raise ValueError(
            f"local update {name!r} already registered (pass overwrite=True to replace)")
    LOCAL_UPDATES[name] = make


def _compile_local(entry: "ClientChain | Callable", loss_fn: Callable, fl: FLConfig):
    """LOCAL_UPDATES entry ->
    (one_client, client_template | None, needs, stateful transform names,
    all transform names)."""
    if isinstance(entry, ClientChain):
        transforms = resolve_chain(entry, loss_fn, fl)
        needs = tuple(dict.fromkeys(k for t in transforms for k in t.needs))
        state_names = tuple(t.name for t in transforms
                            if t.client_init is not None)
        # clients run one at a time stop at their last unmasked step;
        # vmapped cohorts keep the fixed-length scan (build_local_step)
        trim = fl.cohort_mode == "sequential"
        return (build_local_step(transforms, loss_fn, trim_padding=trim),
                chain_client_template(transforms), needs, state_names,
                tuple(t.name for t in transforms))
    inner = entry(loss_fn, fl)  # legacy raw rule: stateless, opt-blind

    def one_client(params, momentum, opt, data, mask, eta, cstate):
        delta, loss = inner(params, momentum, data, mask, eta)
        return delta, loss, cstate

    return one_client, None, (), (), ()


# ---------------------------------------------------------------------------
# Server optimizers.  Simple ones are declared as a `chain` of pseudo-update
# transforms (optax-style) followed by the canonical descent application
# ``x <- x + lr * delta'``; optimizers whose parameter step is not of that
# form (adam) or that maintain a gradient estimate from client data (mvr)
# provide a bespoke whole-state update.
# ---------------------------------------------------------------------------


class ServerTransform(NamedTuple):
    """One link of a server chain.

    ``init(fl, params) -> opt-state slice`` and
    ``update(fl, delta, opt, state, ctx) -> (delta', opt-state updates)``.
    ``provides`` names the opt-state keys ``init`` creates plus any semantic
    capability tags (e.g. the mvr opt's ``grad_estimate``) — client
    transforms declare what they ``need`` against these, and binding
    validates the pairing.  Use a distinct tag when a key name alone would be
    ambiguous across opts.  ``consumes`` names the stateful *client*
    transforms whose cohort state rows (``ctx.cstate``) the update folds in —
    the symmetric check: binding refuses a local chain that keeps none of
    them (the update would silently run without its input).
    """

    init: Callable
    update: Callable
    provides: tuple = ()
    consumes: tuple = ()


def heavy_ball() -> ServerTransform:
    """Classic heavy-ball: m <- beta*m + Delta; the chain then applies lr*m."""

    def init(fl: FLConfig, params):
        return {"m": tree_zeros_like(params)}

    def update(fl: FLConfig, delta, opt, state, ctx):
        m = jax.tree.map(lambda m0, d: fl.momentum * m0 + d, opt["m"], delta)
        return m, {"m": m}

    return ServerTransform(init, update, provides=("m",))


def scaffold_ctl() -> ServerTransform:
    """SCAFFOLD server control variate: ``c <- c + sum_{i in S} (w_i/p_i) *
    (c_i+ - c_i)`` — the w/p-debiased estimate of the population drift of the
    per-client variates the cohort just committed (the paired ``scaffold``
    client transform; O(cohort) per round).  The pseudo-update passes through
    unchanged."""

    def init(fl: FLConfig, params):
        return {"c": tree_zeros_like(params)}

    def update(fl: FLConfig, delta, opt, state, ctx):
        if ctx is None or ctx.cstate is None:
            return delta, {}
        meta = ctx.batch.meta
        wp = meta.valid * meta.weight / meta.prob                    # [C]
        old, new = ctx.cstate.old["scaffold"]["c"], ctx.cstate.new["scaffold"]["c"]
        c = jax.tree.map(
            lambda c0, o, n: (c0.astype(jnp.float32) + jnp.einsum(
                "c,c...->...", wp.astype(jnp.float32),
                n.astype(jnp.float32) - o.astype(jnp.float32))).astype(c0.dtype),
            opt["c"], old, new,
        )
        return delta, {"c": c}

    return ServerTransform(init, update, provides=("c",),
                           consumes=("scaffold",))


class ServerOpt(NamedTuple):
    """A registered server optimizer.

    ``make_update(fl, gen, loss_fn, cohort_mode)`` returns the jit-able
    ``update(state, delta_agg, lr, ctx) -> ServerState``; ``local_update``
    names the client-side rule this optimizer pairs with by default (MVR's
    corrected local steps need the server's gradient estimate) —
    ``FLConfig.local_update`` / ``FedStrategy.local_update`` override it.
    ``provides`` lists the opt-state keys / capability tags client transforms
    may declare a ``need`` on; ``consumes`` lists the stateful client
    transforms whose cohort state the update reads (binding refuses chains
    missing them).
    """

    name: str
    init: Callable                 # (fl, params) -> opt dict
    make_update: Callable
    local_update: str = "sgd"
    provides: tuple = ()
    consumes: tuple = ()


def chain(name: str, *transforms: ServerTransform, local_update: str = "sgd") -> ServerOpt:
    """Compose pseudo-update transforms into a server optimizer ending in the
    descent application ``x <- x + (lr * delta').astype(x.dtype)``."""

    def init(fl: FLConfig, params) -> dict:
        opt: dict = {}
        for t in transforms:
            new = t.init(fl, params)
            dup = set(new) & set(opt)
            if dup:
                raise ValueError(
                    f"server chain {name!r}: transforms collide on opt-state "
                    f"keys {sorted(dup)}")
            opt.update(new)
        return opt

    def make_update(fl: FLConfig, gen, loss_fn, cohort_mode):
        def update(state: ServerState, delta_agg, lr, ctx) -> ServerState:
            opt = dict(state.opt)
            d = delta_agg
            for t in transforms:
                d, new = t.update(fl, d, opt, state, ctx)
                opt.update(new)
            p = jax.tree.map(lambda a, dl: a + (lr * dl).astype(a.dtype),
                             state.params, d)
            return ServerState(params=p, opt=opt, rnd=state.rnd + 1)

        return update

    provides = tuple(dict.fromkeys(k for t in transforms
                                   for k in getattr(t, "provides", ())))
    consumes = tuple(dict.fromkeys(k for t in transforms
                                   for k in getattr(t, "consumes", ())))
    return ServerOpt(name, init, make_update, local_update, provides, consumes)


def _mvr_opt() -> ServerOpt:
    """FedShuffleMVR (§5.1): x still moves by +lr*Delta, but the server
    maintains the gradient estimate m of eq. 14 (exact) or its App. F
    approximation, which clients consume in their corrected local steps."""

    def init(fl: FLConfig, params) -> dict:
        opt = {"m": tree_zeros_like(params)}    # gradient estimate (eq. 14)
        if fl.mvr_exact:
            # own buffers: params is also ServerState.params, and a donated
            # round-0 state must not reference one buffer through two leaves
            opt["x_prev"] = tree_copy(params)
        return opt

    def make_update(fl: FLConfig, gen: GenSpec, loss_fn, cohort_mode):
        def update(state: ServerState, delta_agg, lr, ctx) -> ServerState:
            opt = dict(state.opt)
            if ctx is not None:
                batch, meta = ctx.batch, ctx.batch.meta
                momentum = ctx.momentum
                wp = meta.valid * meta.weight / meta.prob              # [C]
                if fl.mvr_exact:
                    def grads_at(p):
                        if isinstance(batch, BucketedBatch):
                            # per-bucket local gradients, reassembled to [C]
                            # slot order so the wp-weighted reduction below is
                            # bitwise-identical to the padded layout
                            def g(d, m):
                                return full_local_gradient(loss_fn, p, d, m)

                            if cohort_mode == "vmapped":
                                gs = vmap_clients(g, batch)
                                return jax.tree.map(
                                    lambda t: jnp.einsum(
                                        "c,c...->...", wp.astype(jnp.float32), t), gs)
                            gs = scan_clients(g, batch)

                            def accum(acc, xs):
                                G, c = xs
                                return jax.tree.map(
                                    lambda A, Gl: A + c * Gl, acc, G), None

                            acc0 = jax.tree.map(
                                lambda x: jnp.zeros_like(x, jnp.float32), p)
                            out, _ = jax.lax.scan(accum, acc0, (gs, wp))
                            return out
                        if cohort_mode == "vmapped":
                            gs = jax.vmap(
                                lambda d, m: full_local_gradient(loss_fn, p, d, m)
                            )(batch.data, batch.step_mask)
                            return jax.tree.map(
                                lambda t: jnp.einsum(
                                    "c,c...->...", wp.astype(jnp.float32), t), gs)

                        def body(acc, xs):
                            d, m, c = xs
                            g = full_local_gradient(loss_fn, p, d, m)
                            return jax.tree.map(lambda A, G: A + c * G, acc, g), None

                        acc0 = jax.tree.map(
                            lambda x: jnp.zeros_like(x, jnp.float32), p)
                        out, _ = jax.lax.scan(
                            body, acc0, (batch.data, batch.step_mask, wp))
                        return out

                    G_x = grads_at(state.params)
                    G_prev = grads_at(opt["x_prev"])
                    # m_new = G_x + (1-a) * (m - G_prev)   [= eq. 14 rearranged]
                    opt["m"] = jax.tree.map(
                        lambda gx, m, gp: gx + (1.0 - fl.mvr_a)
                        * (m.astype(jnp.float32) - gp),
                        G_x, momentum, G_prev,
                    )
                    opt["x_prev"] = state.params
                else:
                    # App. F: grad-estimate from the aggregated update itself.
                    # With FedShuffle's c_i = K_i, Delta_i ~= -eta_l * mean
                    # grad_i, so g_hat = -Delta_agg / eta_l.  For unscaled-step
                    # strategies (c_i = 1), Delta_i ~= -eta_l * K_i * mean
                    # grad_i, so divide by the cohort-average step count too.
                    if gen.c == "one":
                        wp_sum = jnp.maximum(
                            jnp.sum(meta.valid * meta.weight / meta.prob), 1e-9)
                        k_bar = jnp.sum(meta.valid * (meta.weight / meta.prob)
                                        * meta.num_steps) / wp_sum
                    else:
                        k_bar = 1.0
                    ghat = jax.tree.map(
                        lambda d: -d.astype(jnp.float32)
                        / (fl.local_lr * ctx.lr_mult * k_bar),
                        delta_agg,
                    )
                    opt["m"] = jax.tree.map(
                        lambda g, m: fl.mvr_a * g
                        + (1.0 - fl.mvr_a) * m.astype(jnp.float32),
                        ghat, momentum,
                    )
            p = jax.tree.map(lambda a, d: a + (lr * d).astype(a.dtype),
                             state.params, delta_agg)
            return ServerState(params=p, opt=opt, rnd=state.rnd + 1)

        return update

    return ServerOpt("mvr", init, make_update, local_update="mvr",
                     provides=("m", "grad_estimate"))


def _adam_opt() -> ServerOpt:
    """FedAdam (Reddi et al. 2020) on g = -Delta (beyond-paper)."""

    def init(fl: FLConfig, params) -> dict:
        return {"mu": tree_zeros_like(params), "nu": tree_zeros_like(params)}

    def make_update(fl: FLConfig, gen, loss_fn, cohort_mode):
        def update(state: ServerState, delta_agg, lr, ctx) -> ServerState:
            opt = dict(state.opt)
            b1, b2, eps = 0.9, 0.99, 1e-8
            g = jax.tree.map(lambda d: -d, delta_agg)
            mu = jax.tree.map(lambda m0, gl: b1 * m0 + (1 - b1) * gl, opt["mu"], g)
            nu = jax.tree.map(lambda n0, gl: b2 * n0 + (1 - b2) * gl * gl,
                              opt["nu"], g)
            t = state.rnd.astype(jnp.float32) + 1.0
            mu_hat = jax.tree.map(lambda m0: m0 / (1 - b1**t), mu)
            nu_hat = jax.tree.map(lambda n0: n0 / (1 - b2**t), nu)
            p = jax.tree.map(
                lambda a, m0, n0: a - (lr * m0 / (jnp.sqrt(n0) + eps)).astype(a.dtype),
                state.params, mu_hat, nu_hat,
            )
            opt["mu"], opt["nu"] = mu, nu
            return ServerState(params=p, opt=opt, rnd=state.rnd + 1)

        return update

    return ServerOpt("adam", init, make_update, provides=("mu", "nu"))


SERVER_OPTS: dict[str, ServerOpt] = {
    "sgd": chain("sgd"),
    "momentum": chain("momentum", heavy_ball()),
    "mvr": _mvr_opt(),
    "adam": _adam_opt(),
    # SCAFFOLD: sgd-style descent + server control variate, paired with the
    # stateful "scaffold" client chain (per-client variates in the state bank)
    "scaffold": chain("scaffold", scaffold_ctl(), local_update="scaffold"),
}


def register_server_opt(opt: ServerOpt, *, overwrite: bool = False) -> None:
    if not overwrite and opt.name in SERVER_OPTS:
        raise ValueError(
            f"server opt {opt.name!r} already registered (pass overwrite=True to replace)")
    SERVER_OPTS[opt.name] = opt


def server_opt_init(fl: FLConfig, params) -> dict:
    if fl.server_opt not in SERVER_OPTS:
        raise ValueError(fl.server_opt)
    return SERVER_OPTS[fl.server_opt].init(fl, params)


# ---------------------------------------------------------------------------
# FedStrategy: the declared composition + its registry
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class FedStrategy:
    """A declared (c, w~, q) x server-opt x local-chain composition.

    ``server_opt=None`` defers to ``FLConfig.server_opt`` at bind time, so one
    registered preset covers every server optimizer; ``local_update=None``
    likewise defers to ``FLConfig.local_update`` and then to the server opt's
    paired default — a non-None value *pins* the local chain (binding against
    a disagreeing config raises).  ``equalize`` marks the strategies that
    only make sense with the equalized-K pipeline mode (Table 4's FedAvgMin /
    FedAvgMean): the data pipeline applies it and :func:`bind_strategy`
    refuses configurations that would not.
    """

    name: str
    gen: GenSpec
    server_opt: str | None = None
    equalize: str | None = None       # None | "min" | "mean"
    local_update: str | None = None   # None => FLConfig / server-opt default

    def with_server_opt(self, server_opt: str) -> "FedStrategy":
        return replace(self, server_opt=server_opt)


STRATEGIES: dict[str, FedStrategy] = {}


def register_strategy(strategy: FedStrategy, *, overwrite: bool = False) -> FedStrategy:
    if not overwrite and strategy.name in STRATEGIES:
        raise ValueError(
            f"strategy {strategy.name!r} already registered (pass overwrite=True to replace)")
    if strategy.equalize not in (None, "min", "mean"):
        raise ValueError(
            f"strategy {strategy.name!r}: equalize must be None, 'min' or "
            f"'mean', got {strategy.equalize!r}")
    for slot, kind, registry in (("c", strategy.gen.c, _alg.C_KINDS),
                                 ("w", strategy.gen.w, _alg.W_KINDS),
                                 ("q", strategy.gen.q, _alg.Q_KINDS)):
        if kind not in registry:
            raise ValueError(f"strategy {strategy.name!r}: unknown {slot}-kind {kind!r}")
    STRATEGIES[strategy.name] = strategy
    return strategy


_EQUALIZED_PRESETS = {"fedavg_min": "min", "fedavg_mean": "mean"}
for _name, _gen in PRESETS.items():
    register_strategy(FedStrategy(name=_name, gen=_gen,
                                  equalize=_EQUALIZED_PRESETS.get(_name)))


def strategy_for(algorithm: "str | FLConfig", *, server_opt: str | None = None) -> FedStrategy:
    """Resolve a config string (or a whole FLConfig) to its FedStrategy.

    This is the deprecation shim for the old string-dispatch API: everything
    ``FLConfig.algorithm`` used to select is now a registered composition.
    """
    if isinstance(algorithm, FLConfig):
        return strategy_for(algorithm.algorithm, server_opt=algorithm.server_opt)
    if algorithm not in STRATEGIES:
        raise KeyError(f"unknown strategy {algorithm!r}; have {sorted(STRATEGIES)}")
    s = STRATEGIES[algorithm]
    if server_opt is not None:
        if s.server_opt is None:
            s = s.with_server_opt(server_opt)
        elif s.server_opt != server_opt:
            raise ValueError(
                f"strategy {algorithm!r} pins server_opt={s.server_opt!r}; "
                f"requested {server_opt!r}")
    return s


def equalized_mode(algorithm: str) -> str | None:
    """The equalized-step pipeline mode an algorithm requires (None, "min" or
    "mean").  Raises for unregistered algorithm names so typos fail loudly."""
    return strategy_for(algorithm).equalize


# ---------------------------------------------------------------------------
# Binding: close a FedStrategy over (FLConfig, loss_fn) into pure hooks
# ---------------------------------------------------------------------------


class BoundStrategy(NamedTuple):
    name: str
    gen: GenSpec
    local_update: str                  # static local-chain selection
    equalize: str | None
    fl: FLConfig                       # the config the hooks closed over
    num_clients: int
    loss_fn: Callable                  # the loss the local/server hooks use
    init: Callable                     # (params) -> ServerState
    client_transform: Callable         # (meta, lr_mult) -> ClientPlan
    agg_coeffs: Callable               # (meta) -> [C]
    aggregate: Callable                # (deltas, meta) -> delta_agg
    server_update: Callable            # (state, delta_agg, lr, ctx) -> ServerState
    local_step: Callable               # one_client(params, momentum, opt, data,
    #                                      mask, eta, cstate) -> (delta, loss, cstate')
    client_state: Callable | None = None  # (params) -> one client's state template
    #                                      (None => stateless chain + stateless
    #                                      codec, no bank; includes the codec's
    #                                      "uplink" EF residual when it keeps one)
    codec: Any = None                  # bound fed.comm.Codec (None only for
    #                                      hand-built BoundStrategies: the round
    #                                      driver then skips the uplink entirely)
    robust_aggregate: Callable | None = None  # (deltas, coeff, meta) ->
    #                                      delta_agg — the robustness plane's
    #                                      combiner over explicit coefficients
    #                                      (fl.aggregator; "mean" == the
    #                                      canonical weighted_sum).  The round
    #                                      driver calls it only while the plane
    #                                      is active; None (hand-built
    #                                      strategies) falls back to
    #                                      weighted_sum there.
    down_codec: Any = None             # bound fed.comm.Codec for the downlink
    #                                      broadcast (None for hand-built
    #                                      BoundStrategies: the round driver
    #                                      then broadcasts dense params, the
    #                                      pre-downlink behavior exactly)


def weighted_sum(deltas, coeff: jnp.ndarray):
    """sum_i coeff_i * Delta_i over the leading client axis (fp32 accumulate,
    result cast back to the delta dtype) — the canonical aggregation."""
    return jax.tree.map(
        lambda t: jnp.einsum("c,c...->...", coeff.astype(jnp.float32),
                             t.astype(jnp.float32)).astype(t.dtype),
        deltas,
    )


def bind_strategy(strategy: "FedStrategy | BoundStrategy | None", fl: FLConfig,
                  loss_fn, *, num_clients: int) -> BoundStrategy:
    if isinstance(strategy, BoundStrategy):
        # bind-once-reuse: just validate agreement with what was bound
        if fl is not None and fl != strategy.fl:
            raise ValueError("fl differs from the config this strategy was bound over")
        if num_clients is not None and num_clients != strategy.num_clients:
            raise ValueError("num_clients differs from the bound strategy's")
        if loss_fn is not None and loss_fn is not strategy.loss_fn:
            raise ValueError("loss_fn differs from the one this strategy was bound over")
        return strategy
    if strategy is None:
        strategy = strategy_for(fl)
    # strict on purpose: raises for unregistered fl.algorithm, exactly like
    # the pipeline will — better at bind time than at the first round_batch
    pipeline_mode = equalized_mode(fl.algorithm)
    if pipeline_mode != strategy.equalize:
        # the pipeline keys its K-equalization off FLConfig.algorithm; any
        # disagreement with the strategy silently runs different math than
        # either name promises (equalized strategy on free-K batches == plain
        # FedAvg; free-K strategy on equalized batches == a different recipe)
        raise ValueError(
            f"strategy {strategy.name!r} expects equalized-step pipeline mode "
            f"{strategy.equalize!r}, but FLConfig.algorithm={fl.algorithm!r} "
            f"makes the pipeline apply {pipeline_mode!r}. Set algorithm="
            f"{strategy.name!r} (or register a strategy declaring "
            f"equalize={pipeline_mode!r})."
        )
    if strategy.server_opt is not None and strategy.server_opt != fl.server_opt:
        # a silent override would desync anything keyed off fl.server_opt
        # (legacy init_server, logging/checkpoint metadata) from the actual
        # update rule — e.g. adam opt state fed to a heavy-ball update
        raise ValueError(
            f"strategy {strategy.name!r} pins server_opt="
            f"{strategy.server_opt!r} but FLConfig.server_opt is "
            f"{fl.server_opt!r}; make them agree.")
    if fl.engine not in ("legacy", "cohort"):
        raise ValueError(f"unknown engine {fl.engine!r}; have ('legacy', 'cohort')")
    if fl.exec_mode not in ("padded", "bucketed"):
        raise ValueError(
            f"unknown exec_mode {fl.exec_mode!r}; have ('padded', 'bucketed')")
    if fl.exec_mode == "bucketed" and fl.buckets < 1:
        raise ValueError(f"fl.buckets must be >= 1, got {fl.buckets}")
    # telemetry knobs validated at bind time like every other plane's
    validate_telemetry_config(fl)
    if fleet_active(fl):
        # every fleet-plane knob validated here, mirroring the engine block
        # below: unknown fleet/fault names or bad parameters fail loudly at
        # bind time, not rounds deep into the virtual-clock simulation
        validate_fleet_config(fl)
    if robust_active(fl):
        # robustness-plane knobs (attack / aggregator / guard) likewise fail
        # at bind time, not mid-adversarial-run
        validate_robust_config(fl)
    if fl.engine == "cohort":
        # better a loud bind-time error than a first-round failure deep in the
        # prefetch thread: the engine knobs are all validated here
        from .cohort.engine import _BACKENDS  # deferred: cohort imports rounds
        from .cohort.scheduler import PARTICIPATION

        if fl.rr_backend not in _BACKENDS:
            raise ValueError(
                f"unknown rr_backend {fl.rr_backend!r}; have {_BACKENDS}")
        if fl.participation not in PARTICIPATION:
            raise ValueError(
                f"unknown participation schedule {fl.participation!r}; "
                f"have {sorted(PARTICIPATION)}")
        if fl.prefetch < 0:
            raise ValueError(f"fl.prefetch must be >= 0, got {fl.prefetch}")
    server_opt = strategy.server_opt or fl.server_opt
    if server_opt not in SERVER_OPTS:
        raise ValueError(f"unknown server opt {server_opt!r}; have {sorted(SERVER_OPTS)}")
    sdef = SERVER_OPTS[server_opt]
    # local chain resolution: strategy pin > FLConfig.local_update > the
    # server opt's paired default — with pin/config disagreement an error
    if (strategy.local_update is not None and fl.local_update
            and strategy.local_update != fl.local_update):
        raise ValueError(
            f"strategy {strategy.name!r} pins local_update="
            f"{strategy.local_update!r} but FLConfig.local_update is "
            f"{fl.local_update!r}; make them agree.")
    local_update = strategy.local_update or fl.local_update or sdef.local_update
    if local_update not in LOCAL_UPDATES:
        raise ValueError(
            f"unknown local update {local_update!r}; have {sorted(LOCAL_UPDATES)}")
    local_step, client_state, needs, state_names, transform_names = _compile_local(
        LOCAL_UPDATES[local_update], loss_fn, fl)
    if privacy_active(fl):
        # privacy-plane knobs (dp / secagg) validated against the *resolved*
        # local chain: the ambiguous per-step-clip + DP-clip stack is a
        # bind-time error, not a silently wrong sensitivity bound
        validate_privacy_config(fl, transform_names=transform_names)
    missing_state = [k for k in sdef.consumes if k not in state_names]
    if missing_state:
        # the mirror of the needs/provides check below: a server update that
        # folds in cohort state (e.g. scaffold's control-variate drift) would
        # silently no-op under a chain that keeps none of that state
        raise ValueError(
            f"server opt {server_opt!r} consumes per-client state of client "
            f"transform(s) {missing_state} but local update {local_update!r} "
            f"keeps no such state — the server update would silently run "
            f"without its input.  Pair it with a local update carrying "
            f"{missing_state} (e.g. local_update={missing_state[0]!r}) or "
            f"pick another server opt.")
    missing = [k for k in needs if k not in sdef.provides]
    if missing:
        # the old failure mode was silent: rounds.py zero-fills a missing
        # opt["m"], so e.g. mvr local steps under server_opt="sgd" would
        # quietly degenerate to a (1-a)-biased SGD.  Refuse at bind time.
        raise ValueError(
            f"local update {local_update!r} reads server opt-state key(s) "
            f"{missing} that server opt {server_opt!r} does not maintain "
            f"(provides {list(sdef.provides)}) — the transforms would "
            f"silently consume zeros.  Pick a server opt providing "
            f"{missing} (e.g. "
            + ", ".join(sorted(n for n, o in SERVER_OPTS.items()
                               if all(k in o.provides for k in missing)))
            + ") or a local update that does not need them.")
    # comm plane: both directions resolved and validated here like the local
    # rules (unknown fl.uplink / fl.downlink, direction-incapable codecs and
    # bad knob values fail at bind time, not at the first round)
    codec = build_codec(fl, "uplink")
    down_codec = build_codec(fl, "downlink")
    if UPLINK_STATE_KEY in state_names:
        raise ValueError(
            f"local update {local_update!r} has a stateful client transform "
            f"named {UPLINK_STATE_KEY!r} — that bank key is reserved for the "
            f"uplink codec's error-feedback residual; rename the transform.")
    if DOWNLINK_STATE_KEY in state_names:
        raise ValueError(
            f"local update {local_update!r} has a stateful client transform "
            f"named {DOWNLINK_STATE_KEY!r} — that bank key is reserved for "
            f"the downlink broadcast's client-held reference; rename the "
            f"transform.")
    if codec.client_init is not None:
        chain_state = client_state

        def client_state(params):
            # the codec's EF residual / DIANA shift shares the [N+1, ...]
            # bank with the chain's stateful transforms under the reserved
            # "uplink" key
            d = dict(chain_state(params)) if chain_state is not None else {}
            d[UPLINK_STATE_KEY] = codec.client_init(params)
            return d

    if down_codec.name != "identity":
        pre_down_state = client_state

        def client_state(params):
            # the broadcast reference every client holds — seeded with the
            # init params (server and client agree by construction, and a
            # client skipped by sampling just keeps a stale-but-synced ref)
            d = dict(pre_down_state(params)) if pre_down_state is not None else {}
            d[DOWNLINK_STATE_KEY] = {"ref": params}
            return d

    buffered = fl.server_mode == "buffered"
    if buffered:
        if FLEET_STATE_KEY in state_names:
            raise ValueError(
                f"local update {local_update!r} has a stateful client "
                f"transform named {FLEET_STATE_KEY!r} — that bank key is "
                f"reserved for the buffered server's per-client staleness "
                f"counters; rename the transform.")
        pre_fleet_state = client_state

        def client_state(params):
            # per-client arrival/staleness counters share the bank under the
            # reserved "fleet" key, exactly like the codec's EF residual
            d = dict(pre_fleet_state(params)) if pre_fleet_state is not None else {}
            d[FLEET_STATE_KEY] = fleet_client_state()
            return d

    gen = strategy.gen

    def init(params) -> ServerState:
        # copy: round 0 may donate this state's buffers (jit_round_step), and
        # the caller keeps ownership of the pytree it passed in
        params = tree_copy(params)
        clients = None
        if client_state is not None:
            # one bank row per client + a scratch row (index num_clients) the
            # round driver aims invalid cohort padding at
            tmpl = client_state(params)
            clients = jax.tree.map(
                lambda t: jnp.tile(t[None], (num_clients + 1,) + (1,) * t.ndim),
                tmpl)
        return ServerState(params=params, opt=sdef.init(fl, params),
                           rnd=jnp.zeros((), jnp.int32), clients=clients)

    def client_transform(meta, lr_mult=1.0) -> ClientPlan:
        inv_c = lr_scale(gen, meta)
        return ClientPlan(eta=fl.local_lr * lr_mult * inv_c)

    def agg_coeffs(meta) -> jnp.ndarray:
        # buffered-async: each tick aggregates |S| = buffer_size arrivals (the
        # q normalization's cohort size) and discounts stale updates; the sync
        # path multiplies nothing — bitwise-frozen
        coeff = agg_coeff(gen, meta, num_clients=num_clients,
                          cohort_size=fl.buffer_size if buffered else fl.cohort_size)
        if buffered:
            coeff = coeff * staleness_weights(fl, meta)
        return coeff

    def aggregate(deltas, meta):
        return weighted_sum(deltas, agg_coeffs(meta))

    # the robustness plane's combiner: same coefficients (agg_coeffs stays
    # THE weight primitive — staleness discounts and all), explicit so the
    # round driver can renormalize them after a quarantine.  "mean" binds
    # the canonical weighted_sum, so swapping aggregators never rescales
    # the server step.
    robust_aggregate = build_robust_aggregate(fl)

    return BoundStrategy(
        name=strategy.name,
        gen=gen,
        local_update=local_update,
        equalize=strategy.equalize,
        fl=fl,
        num_clients=num_clients,
        loss_fn=loss_fn,
        init=init,
        client_transform=client_transform,
        agg_coeffs=agg_coeffs,
        aggregate=aggregate,
        server_update=sdef.make_update(fl, gen, loss_fn, fl.cohort_mode),
        local_step=local_step,
        client_state=client_state,
        codec=codec,
        robust_aggregate=robust_aggregate,
        down_codec=down_codec,
    )


def apply_server_opt(fl: FLConfig, state: ServerState, delta, lr) -> ServerState:
    """Legacy one-shot server application (no round context): runs the
    configured optimizer's parameter step on an aggregated pseudo-update."""
    if fl.server_opt not in SERVER_OPTS:
        raise ValueError(fl.server_opt)
    sdef = SERVER_OPTS[fl.server_opt]
    return sdef.make_update(fl, None, None, fl.cohort_mode)(state, delta, lr, None)
