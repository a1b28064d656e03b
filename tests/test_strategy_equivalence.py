"""Preset-equivalence: the composable FedStrategy path must reproduce the
seed (pre-strategy-API) round math EXACTLY — same aggregated delta, same
ServerState (params + optimizer trees + round counter), same metrics — for
all 8 algorithm presets x both cohort modes x {sgd, momentum, mvr-approx,
adam} (+ mvr-exact spot checks), on the paper's duplicated-quadratic problem.

``_seed_*`` below is a frozen copy of the original monolithic implementation
(git 58efe7d: core/algorithms.py + fed/server.py + fed/rounds.py), kept
verbatim so any drift in the refactored engine fails loudly.  Both paths run
eagerly (no jit) so the primitive sequences — which are identical — produce
bitwise-identical floats.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FLConfig
from repro.core.algorithms import PRESETS
from repro.core.local import full_local_gradient, local_mvr, local_sgd
from repro.data.federated import FederatedPipeline, Population
from repro.data.tasks import DuplicatedQuadraticTask
from repro.fed.losses import make_quadratic_loss
from repro.fed.rounds import as_device_batch, build_round_step
from repro.fed.server import ServerState
from repro.fed.strategy import bind_strategy, strategy_for
from repro.utils.pytree import tree_zeros_like

TASK = DuplicatedQuadraticTask(copies=(1, 2, 3))
LOSS = make_quadratic_loss(3)

# (c, w, q) of the seed PRESETS table — duplicated on purpose: if the live
# registry drifts, the equivalence below must fail against THIS table.
_SEED_PRESETS = {
    "fedshuffle": ("steps", "w", "p"),
    "fedavg": ("one", "w", "p"),
    "fedavg_so": ("one", "w", "sum_one"),
    "fedshuffle_so": ("steps", "w", "sum_one"),
    "fednova": ("one", "nova", "p"),
    "fedavg_min": ("one", "w", "p"),
    "fedavg_mean": ("one", "w", "p"),
    "gen": ("steps_planned", "nova_actual", "p"),
}


def _seed_lr_scale(c_kind, meta):
    steps = jnp.maximum(meta.num_steps, 1.0)
    planned = jnp.maximum(meta.num_steps_planned, 1.0)
    if c_kind == "one":
        return jnp.ones_like(steps)
    if c_kind in ("steps", "steps_planned"):
        return 1.0 / planned
    raise ValueError(c_kind)


def _seed_agg_coeff(w_kind, q_kind, meta, *, num_clients, cohort_size):
    w, p, valid = meta.weight, meta.prob, meta.valid
    steps = jnp.maximum(meta.num_steps, 1.0)
    planned = jnp.maximum(meta.num_steps_planned, 1.0)
    if w_kind == "w":
        wt = w
    elif w_kind == "nova":
        tau_eff = jnp.sum(valid * (w / p) * steps)
        wt = w * tau_eff / steps
    elif w_kind == "nova_actual":
        wt = w * planned / steps
    else:
        raise ValueError(w_kind)
    if q_kind == "p":
        q = p
    elif q_kind == "sum_one":
        q = jnp.sum(valid * w) * (cohort_size / num_clients)
        q = jnp.maximum(q, 1e-12)
    else:
        raise ValueError(q_kind)
    return valid * wt / q


def _seed_init_server(fl, params):
    opt = {}
    if fl.server_opt == "momentum":
        opt["m"] = tree_zeros_like(params)
    elif fl.server_opt == "mvr":
        opt["m"] = tree_zeros_like(params)
        if fl.mvr_exact:
            opt["x_prev"] = params
    elif fl.server_opt == "adam":
        opt["mu"] = tree_zeros_like(params)
        opt["nu"] = tree_zeros_like(params)
    return ServerState(params=params, opt=opt, rnd=jnp.zeros((), jnp.int32))


def _seed_apply_server(fl, state, delta, lr):
    p, opt = state.params, dict(state.opt)
    if fl.server_opt == "sgd" or fl.server_opt == "mvr":
        p = jax.tree.map(lambda a, d: a + (lr * d).astype(a.dtype), p, delta)
    elif fl.server_opt == "momentum":
        m = jax.tree.map(lambda m0, d: fl.momentum * m0 + d, opt["m"], delta)
        opt["m"] = m
        p = jax.tree.map(lambda a, m0: a + (lr * m0).astype(a.dtype), p, m)
    elif fl.server_opt == "adam":
        b1, b2, eps = 0.9, 0.99, 1e-8
        g = jax.tree.map(lambda d: -d, delta)
        mu = jax.tree.map(lambda m0, gl: b1 * m0 + (1 - b1) * gl, opt["mu"], g)
        nu = jax.tree.map(lambda n0, gl: b2 * n0 + (1 - b2) * gl * gl, opt["nu"], g)
        t = state.rnd.astype(jnp.float32) + 1.0
        mu_hat = jax.tree.map(lambda m0: m0 / (1 - b1**t), mu)
        nu_hat = jax.tree.map(lambda n0: n0 / (1 - b2**t), nu)
        p = jax.tree.map(
            lambda a, m0, n0: a - (lr * m0 / (jnp.sqrt(n0) + eps)).astype(a.dtype),
            p, mu_hat, nu_hat,
        )
        opt["mu"], opt["nu"] = mu, nu
    else:
        raise ValueError(fl.server_opt)
    return ServerState(params=p, opt=opt, rnd=state.rnd + 1)


def _seed_build_round_step(loss_fn, fl, num_clients):
    c_kind, w_kind, q_kind = _SEED_PRESETS[fl.algorithm]
    use_mvr = fl.server_opt == "mvr"

    def one_client(params, momentum, data_i, mask_i, eta_i):
        if use_mvr:
            return local_mvr(loss_fn, params, momentum, data_i, mask_i, eta_i, fl.mvr_a)
        return local_sgd(loss_fn, params, data_i, mask_i, eta_i)

    def round_step(state, batch, lr_mult=1.0):
        meta = batch.meta
        inv_c = _seed_lr_scale(c_kind, meta)
        coeff = _seed_agg_coeff(w_kind, q_kind, meta, num_clients=num_clients,
                                cohort_size=fl.cohort_size)
        eta = fl.local_lr * lr_mult * inv_c
        momentum = state.opt.get("m", None)
        if momentum is None:
            momentum = tree_zeros_like(state.params)

        if fl.cohort_mode == "vmapped":
            deltas, losses = jax.vmap(
                lambda d, m, e: one_client(state.params, momentum, d, m, e)
            )(batch.data, batch.step_mask, eta)
            delta_agg = jax.tree.map(
                lambda t: jnp.einsum("c,c...->...", coeff.astype(jnp.float32),
                                     t.astype(jnp.float32)).astype(t.dtype),
                deltas,
            )
        else:
            def body(acc, xs):
                data_i, mask_i, eta_i, coeff_i = xs
                delta, loss = one_client(state.params, momentum, data_i, mask_i, eta_i)
                acc = jax.tree.map(
                    lambda A, D: (A + coeff_i * D.astype(jnp.float32)).astype(A.dtype),
                    acc, delta,
                )
                return acc, loss

            acc_dt = jnp.dtype(fl.accum_dtype)
            acc0 = jax.tree.map(lambda x: jnp.zeros_like(x, acc_dt), state.params)
            delta_agg, losses = jax.lax.scan(
                body, acc0, (batch.data, batch.step_mask, eta, coeff)
            )
            delta_agg = jax.tree.map(lambda a, p: a.astype(p.dtype), delta_agg, state.params)

        new_opt = dict(state.opt)
        if use_mvr:
            wp = meta.valid * meta.weight / meta.prob
            if fl.mvr_exact:
                def grads_at(p):
                    if fl.cohort_mode == "vmapped":
                        gs = jax.vmap(lambda d, m: full_local_gradient(loss_fn, p, d, m))(
                            batch.data, batch.step_mask)
                        return jax.tree.map(
                            lambda t: jnp.einsum("c,c...->...", wp.astype(jnp.float32), t), gs)

                    def body(acc, xs):
                        d, m, c = xs
                        g = full_local_gradient(loss_fn, p, d, m)
                        return jax.tree.map(lambda A, G: A + c * G, acc, g), None
                    acc0 = jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), p)
                    out, _ = jax.lax.scan(body, acc0, (batch.data, batch.step_mask, wp))
                    return out

                G_x = grads_at(state.params)
                G_prev = grads_at(state.opt["x_prev"])
                new_opt["m"] = jax.tree.map(
                    lambda gx, m, gp: gx + (1.0 - fl.mvr_a) * (m.astype(jnp.float32) - gp),
                    G_x, momentum, G_prev,
                )
                new_opt["x_prev"] = state.params
            else:
                if c_kind == "one":
                    wp_sum = jnp.maximum(jnp.sum(meta.valid * meta.weight / meta.prob), 1e-9)
                    k_bar = jnp.sum(meta.valid * (meta.weight / meta.prob)
                                    * meta.num_steps) / wp_sum
                else:
                    k_bar = 1.0
                ghat = jax.tree.map(
                    lambda d: -d.astype(jnp.float32) / (fl.local_lr * lr_mult * k_bar),
                    delta_agg,
                )
                new_opt["m"] = jax.tree.map(
                    lambda g, m: fl.mvr_a * g + (1.0 - fl.mvr_a) * m.astype(jnp.float32),
                    ghat, momentum,
                )

        state = ServerState(params=state.params, opt=new_opt, rnd=state.rnd)
        state = _seed_apply_server(fl, state, delta_agg, jnp.asarray(fl.server_lr, jnp.float32))

        valid_sum = jnp.maximum(meta.valid.sum(), 1.0)
        metrics = {
            "local_loss": (losses * meta.valid).sum() / valid_sum,
            "delta_norm": jnp.sqrt(
                sum(jnp.sum(x.astype(jnp.float32) ** 2) for x in jax.tree.leaves(delta_agg))
            ),
            "cohort": meta.valid.sum(),
        }
        return state, metrics

    return round_step


# ---------------------------------------------------------------------------
# the comparison harness
# ---------------------------------------------------------------------------

N_ROUNDS = 3


def _fl(preset, mode, opt, exact=False):
    # epochs=2 + drop_last_steps=1 makes planned != actual steps, exercising
    # the planned/actual split of "gen"; 2-of-3 uniform sampling exercises
    # valid-masking and inclusion probabilities.
    return FLConfig(num_clients=3, cohort_size=2, sampling="uniform", epochs=2,
                    local_batch=1, algorithm=preset, local_lr=0.05, server_lr=0.8,
                    server_opt=opt, mvr_a=0.2, mvr_exact=exact, cohort_mode=mode,
                    drop_last_steps=1, seed=11)


def _assert_tree_equal(a, b, what):
    ja, jb = jax.tree.flatten(a)[0], jax.tree.flatten(b)[0]
    assert jax.tree.structure(a) == jax.tree.structure(b), what
    for x, y in zip(ja, jb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), err_msg=what)


def _run_both(fl):
    pipe = FederatedPipeline(TASK, Population.build(fl, sizes=TASK.sizes()), fl)
    batches = [as_device_batch(pipe.round_batch(r)) for r in range(N_ROUNDS)]
    params = {"x": jnp.array([0.3, -0.1, 0.2], jnp.float32)}

    seed_step = _seed_build_round_step(LOSS, fl, num_clients=fl.num_clients)
    seed_state = _seed_init_server(fl, params)

    strategy = bind_strategy(strategy_for(fl), fl, LOSS, num_clients=fl.num_clients)
    new_step = build_round_step(LOSS, strategy, fl, num_clients=fl.num_clients)
    new_state = strategy.init(params)

    _assert_tree_equal(seed_state.opt, new_state.opt, "init opt state")
    for r in range(N_ROUNDS):
        seed_state, seed_mets = seed_step(seed_state, batches[r])
        new_state, new_mets = new_step(new_state, batches[r])
    return (seed_state, seed_mets), (new_state, new_mets)


@pytest.mark.parametrize("mode", ["vmapped", "sequential"])
@pytest.mark.parametrize("opt", ["sgd", "momentum", "mvr", "adam"])
def test_all_presets_match_seed(mode, opt):
    for preset in PRESETS:
        fl = _fl(preset, mode, opt)
        (ss, sm), (ns, nm) = _run_both(fl)
        tag = f"{preset}/{mode}/{opt}"
        _assert_tree_equal(ss.params, ns.params, f"{tag}: params")
        _assert_tree_equal(ss.opt, ns.opt, f"{tag}: opt state")
        np.testing.assert_array_equal(np.asarray(ss.rnd), np.asarray(ns.rnd), tag)
        _assert_tree_equal(sm, nm, f"{tag}: metrics")


@pytest.mark.parametrize("mode", ["vmapped", "sequential"])
def test_mvr_exact_matches_seed(mode):
    fl = _fl("fedshuffle", mode, "mvr", exact=True)
    (ss, sm), (ns, nm) = _run_both(fl)
    _assert_tree_equal(ss.params, ns.params, "mvr-exact params")
    _assert_tree_equal(ss.opt, ns.opt, "mvr-exact opt state")
    _assert_tree_equal(sm, nm, "mvr-exact metrics")


def test_legacy_signature_matches_new_api():
    """build_round_step(loss_fn, fl=..., num_clients=...), the FLConfig's
    strings resolved through the registry, must produce the exact same
    trajectory as the explicit-strategy call; the retired form with the
    FLConfig in the strategy's place fails loudly."""
    fl = _fl("fedshuffle", "vmapped", "momentum")
    pipe = FederatedPipeline(TASK, Population.build(fl, sizes=TASK.sizes()), fl)
    batch = as_device_batch(pipe.round_batch(0))
    params = {"x": jnp.zeros(3)}

    strategy = bind_strategy(strategy_for(fl), fl, LOSS, num_clients=3)
    s_new, m_new = build_round_step(LOSS, strategy, fl, num_clients=3)(
        strategy.init(params), batch)
    s_old, m_old = build_round_step(LOSS, fl=fl, num_clients=3)(
        strategy.init(params), batch)
    _assert_tree_equal(s_new.params, s_old.params, "registry params")
    _assert_tree_equal(s_new.opt, s_old.opt, "registry opt")
    _assert_tree_equal(m_new, m_old, "registry metrics")

    # positional num_clients must also resolve
    s_pos, _ = build_round_step(LOSS, None, fl, 3)(strategy.init(params), batch)
    _assert_tree_equal(s_new.params, s_pos.params, "positional params")
    with pytest.raises(TypeError):
        build_round_step(LOSS, fl, num_clients=3)
    with pytest.raises(TypeError):
        build_round_step(LOSS, fl, fl)
