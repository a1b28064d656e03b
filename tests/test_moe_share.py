"""The share-holding dropless expert layer (models/moe.py): shares of the
routed experts add up to the uncut layer, the grouped dispatch equals a
per-expert loop on the same routing, and no (token, expert) pair is lost."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig, MoEConfig
from repro.models.layers import swiglu
from repro.models.moe import moe_forward, moe_init, row_bound

D, E, K, F = 64, 8, 3, 32


def _cfg(held=0, first_held=0, num_shared=1):
    return ArchConfig(name="moe-test", family="moe", d_model=D, dtype="float32",
                      moe=MoEConfig(num_experts=E, top_k=K, num_shared=num_shared,
                                    expert_ff=F, held=held, first_held=first_held,
                                    aux_alpha=0.01))


def _uncut(seed=0, router_scale=1.0):
    p = moe_init(jax.random.PRNGKey(seed), _cfg(), jnp.float32)
    return {**p, "router": p["router"] * router_scale}


def _share(p, first, held):
    ex = jax.tree.map(lambda t: t[first:first + held], p["experts"])
    return {**p, "experts": ex}


def _x(seed=1, B=2, T=24):
    return jax.random.normal(jax.random.PRNGKey(seed), (B, T, D), jnp.float32)


def _loop(p, cfg, x):
    """Per-expert loop on the layer's own routing: every token, for every
    held expert it chose, adds gate * FFN_e(x_t); plus the shared experts."""
    m = cfg.moe
    toks = x.reshape(-1, D)
    probs = jax.nn.softmax(toks @ p["router"], axis=-1)
    gates, idx = jax.lax.top_k(probs, m.top_k)
    y = jnp.zeros_like(toks)
    for j in range(m.held_experts()):
        e = m.first_held + j
        w = jnp.sum(jnp.where(idx == e, gates, 0.0), axis=-1)           # [N]
        ex = jax.tree.map(lambda t: t[j], p["experts"])
        y = y + w[:, None] * swiglu(ex, toks)
    y = y + swiglu(p["shared"], toks)
    held = (idx >= m.first_held) & (idx < m.first_held + m.held_experts())
    return y.reshape(x.shape), int(jnp.sum(held))


def test_shares_sum_to_the_uncut_layer_forward_and_gradient():
    """Two shares of four experts each: their routed parts, with the shared
    expert counted once, give the uncut layer's output, and the gradients of
    the same loss: each share's experts get the uncut layer's rows of the
    expert gradient, the router and the input the sum over shares."""
    p, x = _uncut(), _x()
    cot = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    whole, (s0, s1) = _cfg(), (_cfg(held=4, first_held=0), _cfg(held=4, first_held=4))

    def uncut_loss(p, x):
        y, _, _ = moe_forward(p, whole, x)
        return jnp.sum(y * cot)

    def shares_loss(p0, p1, x):
        y0, _, _ = moe_forward(p0, s0, x)
        y1, _, _ = moe_forward(p1, s1, x)
        return jnp.sum((y0 + y1 - swiglu(p0["shared"], x)) * cot)

    y, aux, rows = moe_forward(p, whole, x)
    y0, aux0, r0 = moe_forward(_share(p, 0, 4), s0, x)
    y1, aux1, r1 = moe_forward(_share(p, 4, 4), s1, x)
    np.testing.assert_allclose(y0 + y1 - swiglu(p["shared"], x), y, rtol=1e-5, atol=1e-6)
    # every chip computes the balance loss alike, over all experts
    assert float(aux0) == float(aux1) == float(aux)
    assert int(r0) + int(r1) == int(rows) == 2 * 24 * K

    g, gx = jax.grad(uncut_loss, argnums=(0, 1))(p, x)
    g0, g1, gx_s = jax.grad(shares_loss, argnums=(0, 1, 2))(_share(p, 0, 4), _share(p, 4, 4), x)
    for name in ("gate", "up", "down"):
        np.testing.assert_allclose(
            jnp.concatenate([g0["experts"][name], g1["experts"][name]]),
            g["experts"][name], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(g0["router"] + g1["router"], g["router"], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(gx_s, gx, rtol=1e-5, atol=1e-6)
    for name in ("gate", "up", "down"):   # the shared expert, counted once
        np.testing.assert_allclose(g0["shared"][name] + g1["shared"][name], g["shared"][name],
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("held,first_held", [(0, 0), (4, 4), (2, 3), (1, 7)])
@pytest.mark.parametrize("router_scale", [1.0, 40.0])
def test_dropless_layer_equals_a_per_expert_loop(held, first_held, router_scale):
    """On the same routing the grouped dispatch gives the per-expert loop's
    output and counts every held (token, choice) pair; a sharp router (scale
    40) piles most pairs on few experts, which a capacity would drop."""
    cfg = _cfg(held=held, first_held=first_held)
    p = _share(_uncut(router_scale=router_scale), first_held, cfg.moe.held_experts())
    x = _x()
    y, _, rows = moe_forward(p, cfg, x)
    want, n_held = _loop(p, cfg, x)
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    assert int(rows) == n_held


def _routed_here(p, first, held):
    """p's router biased so that every token of a positive input chooses the
    held experts ``first .. first + held - 1``."""
    bias = jnp.zeros((D, E)).at[:, first:first + held].set(1.0)
    return {**p, "router": p["router"] + bias}


def test_every_token_routed_here_fits_the_row_bound():
    """A router that sends every token to the held experts routes the most
    rows a share can take, tokens * min(top_k, held), which the static row
    bound (that, up to whole row tiles) holds: nothing is dropped."""
    held, first = 2, 5
    cfg = _cfg(held=held, first_held=first)
    p = _routed_here(_share(_uncut(), first, held), first, held)
    x = jnp.abs(_x()) + 1.0
    y, _, rows = moe_forward(p, cfg, x)
    want, n_held = _loop(p, cfg, x)
    assert int(rows) == n_held == 48 * held
    assert row_bound(cfg, 48) == 128 and row_bound(cfg, 64 * 48) == 48 * 64 * held
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("held,first_held,router_scale,routed_here", [
    *((h, f, s, False) for h, f in [(0, 0), (4, 4), (2, 3), (1, 7)] for s in (1.0, 40.0)),
    (2, 5, 1.0, True),
])
def test_layer_gradient_equals_a_per_expert_loops(held, first_held, router_scale,
                                                  routed_here):
    """On the same routing the layer's gradient, through its gather-only
    dispatch and combine, equals the per-expert loop's for the routed and
    shared experts, the router and the input; with every token routed here
    (64 tokens, 2 held) the pairs fill all 128 static rows."""
    cfg = _cfg(held=held, first_held=first_held)
    p = _share(_uncut(router_scale=router_scale), first_held, cfg.moe.held_experts())
    x = _x()
    if routed_here:
        p, x = _routed_here(p, first_held, held), jnp.abs(_x(T=32)) + 1.0
        assert int(moe_forward(p, cfg, x)[2]) == row_bound(cfg, 64) == 128
    cot = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    got = jax.grad(lambda p, x: jnp.sum(moe_forward(p, cfg, x)[0] * cot), (0, 1))(p, x)
    want = jax.grad(lambda p, x: jnp.sum(_loop(p, cfg, x)[0] * cot), (0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):   # to each leaf's scale
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5 * max(1.0, float(jnp.max(jnp.abs(b)))))


def test_balance_loss_is_deepseek_v2_sequence_wise():
    """alpha * mean over sequences of sum_e f_e P_e, f_e = E / (k T) times
    the tokens of the sequence that chose e, P_e the sequence's mean score."""
    cfg, p, x = _cfg(), _uncut(), _x()
    _, aux, _ = moe_forward(p, cfg, x)
    probs = np.asarray(jax.nn.softmax(x @ p["router"], axis=-1), np.float64)   # [B, T, E]
    B, T, _ = probs.shape
    want = 0.0
    for b in range(B):
        top = np.argsort(-probs[b], axis=-1)[:, :K]
        f = np.array([np.sum(top == e) for e in range(E)]) * E / (K * T)
        want += np.sum(f * probs[b].mean(axis=0))
    np.testing.assert_allclose(float(aux), 0.01 * want / B, rtol=1e-5)


def test_a_vmapped_cohort_runs_each_member_alone():
    """Under vmap (a vmapped cohort of clients, each with its own weights)
    the layer and its gradient equal those of each member run alone."""
    cfg = _cfg(held=4, first_held=2)
    ps = [_share(_uncut(seed=s), 2, 4) for s in (0, 3)]
    xs = jnp.stack([_x(1), _x(4)])
    stacked = jax.tree.map(lambda a, b: jnp.stack([a, b]), *ps)

    def loss(p, x):
        y, aux, _ = moe_forward(p, cfg, x)
        return jnp.sum(y * y) + aux

    got = jax.vmap(jax.value_and_grad(loss))(stacked, xs)
    for i, p in enumerate(ps):
        val, g = jax.value_and_grad(loss)(p, xs[i])
        np.testing.assert_allclose(got[0][i], val, rtol=1e-5)
        for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(g)):
            np.testing.assert_allclose(a[i], b, rtol=1e-4, atol=1e-6)


def test_held_experts_default_to_all():
    m = MoEConfig(num_experts=64, top_k=6)
    assert m.held_experts() == 64
    assert dataclasses.replace(m, held=8).held_experts() == 8
