"""Mixture-of-Experts (DeepSeekMoE, arXiv:2401.06066 / 2405.04434): a softmax
top-k router over all routed experts, the layer's share of them computed
dropless with a grouped matmul, shared experts, and the sequence-wise
balance loss.

A layer may hold a share of the routed experts (``MoEConfig.held`` from
``first_held``), as one chip of an expert-parallel deployment holds its own:
the router scores all ``num_experts``, each token keeps its greedy top-k with
unnormalised gates, and the layer computes the part of the output that its
held experts give.  The (token, choice) pairs routed to held experts are
sorted by expert and run through one grouped matmul per projection, whose
work follows the rows routed (megablox ``gmm``, which the chip ran at 96
rows an expert 2.1x as fast as ``jax.lax.ragged_dot``); no pair is dropped.
What the absent experts would add is left out: the other shares of a
deployment add it.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.custom_batching import sequential_vmap
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

from ..configs.base import ArchConfig
from ..obs import trace
from .layers import dense_init, swiglu, swiglu_init


def moe_init(key, cfg: ArchConfig, dtype):
    m = cfg.moe
    D, n = cfg.d_model, m.held_experts()
    ks = jax.random.split(key, 5)
    ek = jax.random.split(ks[0], 3)
    p = {
        "router": dense_init(ks[1], D, m.num_experts, dtype),
        "experts": {
            "gate": jax.vmap(lambda k: dense_init(k, D, m.expert_ff, dtype))(
                jax.random.split(ek[0], n)
            ),
            "up": jax.vmap(lambda k: dense_init(k, D, m.expert_ff, dtype))(
                jax.random.split(ek[1], n)
            ),
            "down": jax.vmap(lambda k: dense_init(k, m.expert_ff, D, dtype))(
                jax.random.split(ek[2], n)
            ),
        },
    }
    if m.num_shared:
        p["shared"] = swiglu_init(ks[2], D, m.expert_ff * m.num_shared, dtype)
    return p


# gmm's (rows, contraction, output) tiles: of those tried on a v5e at 96 rows
# an expert, the fastest forward and backward
TILING = (128, 512, 512)


def row_bound(cfg: ArchConfig, tokens: int) -> int:
    """Static rows of the grouped matmul: every (token, choice) pair a held
    expert can take, ``tokens * min(top_k, held)``, up to whole row tiles."""
    pairs = tokens * min(cfg.moe.top_k, cfg.moe.held_experts())
    return -(-pairs // TILING[0]) * TILING[0]


def seq_balance_loss(probs, idx, num_experts: int, alpha: float):
    """DeepSeek-V2's sequence-wise balance loss over all experts.

    probs [B, T, E] router scores, idx [B, T, k] the chosen experts; per
    sequence f_e = E / (k T) * #{t : e in topk(t)} and P_e = mean_t s_{t,e};
    the loss is alpha * mean over sequences of sum_e f_e P_e."""
    _, T, k = idx.shape
    counts = jnp.sum(jax.nn.one_hot(idx, num_experts, dtype=jnp.float32), axis=(1, 2))
    f = counts * (num_experts / (k * T))                              # [B, E]
    return alpha * jnp.mean(jnp.sum(f * jnp.mean(probs, axis=1), axis=-1))


def moe_forward(params, cfg: ArchConfig, x):
    """x [B, T, D] -> (y [B, T, D], balance loss, rows computed)."""
    m = cfg.moe
    B, T, D = x.shape
    E, k, n = m.num_experts, m.top_k, m.held_experts()
    N = B * T
    R = row_bound(cfg, N)
    trace.counter("model/moe", experts=E, held=n, first_held=m.first_held, top_k=k,
                  row_bound=R)
    tokens = x.reshape(N, D)
    ex = params["experts"]

    with jax.named_scope("router"):
        logits = tokens.astype(jnp.float32) @ params["router"].astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)                       # [N, E]
        gates, idx = jax.lax.top_k(probs, k)                          # [N, k]
        aux = seq_balance_loss(probs.reshape(B, T, E), idx.reshape(B, T, k), E,
                               m.aux_alpha)

    with jax.named_scope("moe_dispatch"):
        local = idx.reshape(-1) - m.first_held                        # [N k]
        held = (local >= 0) & (local < n)
        group = jnp.where(held, local, n)           # pairs of absent experts sort last
        order = jnp.argsort(group, stable=True)[:R]
        sizes = jnp.bincount(group, length=n + 1)[:n].astype(jnp.int32)
        rows = jnp.sum(sizes)
        live = jnp.arange(R) < rows                                   # [R]
        pair = jnp.pad(order, (0, R - order.shape[0]))               # [R] each row's pair
        # each held pair's row, the inverse of ``pair``: its group's first row
        # plus the pairs of its group before it (0 for pairs held elsewhere)
        mine = (group[:, None] == jnp.arange(n)).astype(jnp.int32)   # [N k, n]
        before = jnp.cumsum(mine, axis=0) - mine + (jnp.cumsum(sizes) - sizes)
        slot = jnp.sum(mine * before, axis=1)                        # [N k]
        # rows past the groups are never written by the grouped matmul: mask them
        xs = _take_rows("moe_dispatch", tokens, pair // k, live,
                        slot.reshape(N, k), held.reshape(N, k))      # [R, D]

    with jax.named_scope("experts"):
        h = jax.nn.silu(_grouped(xs, ex["gate"], sizes)) * _grouped(xs, ex["up"], sizes)
        ys = jnp.where(live[:, None], _grouped(h, ex["down"], sizes), 0)

    with jax.named_scope("moe_combine"):
        w = jnp.where(held, gates.reshape(-1), 0.0).reshape(N, k)
        picked = _take_rows("moe_combine", ys, slot, held,
                            pair[:, None], live[:, None]).reshape(N, k, D)
        y = jnp.einsum("nk,nkd->nd", w, picked.astype(jnp.float32)).astype(x.dtype)

    y = y.reshape(B, T, D)
    if m.num_shared:
        with jax.named_scope("shared_experts"):
            y = y + swiglu(params["shared"], x)
    return y, aux, rows


def _gmm(lhs, rhs, sizes):
    # megablox.gmm is a custom_vjp with static arguments: pass them by position;
    # off the TPU (CPU tests) the kernel runs in interpret mode
    return megablox.gmm(lhs, rhs, sizes, lhs.dtype, TILING, None, None, False,
                        jax.default_backend() != "tpu")


def _gmm_vjp(lhs, rhs, sizes, g):
    return jax.vjp(lambda a, b: _gmm(a, b, sizes), lhs, rhs)[1](g)


# A batch of grouped matmuls (a vmapped cohort) runs one member at a time:
# the kernel has no batching rule for its group metadata.
_gmm_one = sequential_vmap(_gmm)
_gmm_vjp_one = sequential_vmap(_gmm_vjp)


@jax.custom_vjp
def _grouped(lhs, rhs, sizes):
    """Rows of ``lhs`` [R, K] in consecutive groups of ``sizes`` [G] times
    ``rhs`` [G, K, F]; rows past the groups are left unwritten."""
    return _gmm_one(lhs, rhs, sizes)


def _grouped_fwd(lhs, rhs, sizes):
    return _gmm_one(lhs, rhs, sizes), (lhs, rhs, sizes)


def _grouped_bwd(res, g):
    lhs, rhs, sizes = res
    d_lhs, d_rhs = _gmm_vjp_one(lhs, rhs, sizes, g)
    return d_lhs, d_rhs, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _take_rows(scope, x, idx, ok, back, back_ok):
    """Rows ``x[idx]`` [I, D] where ``ok``, else zero, for a one-to-one pairing
    of rows that ``back`` [M, g] gives the other way round: each row of ``x``
    went to at most ``g`` output rows, those of ``back`` where ``back_ok``.
    The transpose is then a gather by ``back``, summed over ``g`` in float32
    and run under the named scope ``scope``; XLA's own would sort the indices
    and scatter-add rows, far below the HBM roof."""
    return jnp.where(ok[:, None], x[idx], 0)


def _take_rows_fwd(scope, x, idx, ok, back, back_ok):
    return _take_rows(scope, x, idx, ok, back, back_ok), (back, back_ok)


def _take_rows_bwd(scope, res, g):
    back, back_ok = res
    with jax.named_scope(scope):
        d_x = jnp.where(back_ok[..., None], g[back].astype(jnp.float32), 0)
        return jnp.sum(d_x, axis=1).astype(g.dtype), None, None, None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)
