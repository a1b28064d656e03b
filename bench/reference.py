"""Plain reference of the benchmark's federated rounds, in float32.

Independent of the program: it imports nothing from ``src/`` and derives
everything from the seed and the cell's files.

* ``round_plan`` — who trains in round r and on which token rows: the
  clients' sizes and token rows from the benchmark's own ``bench/clients.py``;
  the uniform cohort and each client's reshuffled batches from the seeded
  numpy streams that FedShuffle's sampler and reshuffle define.
* The model — the configuration's family (``bench/families/<family>.py``)
  gives its layers in order, each a float32 function of its weights and the
  hidden state that returns the new state and the layer's auxiliary loss;
  the embedding, final RMSNorm and head (tied or separate) are shared here.
  Every matrix product is at ``Precision.HIGHEST``.
* ``local_step`` — one SGD step on one batch of the loss cross entropy +
  the layers' auxiliary losses.  The backward pass runs layer by layer and
  updates each layer as soon as its gradient is known, so one full-width
  copy of the gradient never exists and the 1.3B-parameter cell fits one
  chip.
* ``reference_rounds`` — FedShuffle rounds: each cohort client runs its
  K_i local steps at ``local_lr / K_i``; the server adds
  ``server_lr * sum_i (w_i / p_i) (y_i - x)``.

The parameters are held in the configuration's dtype between steps, as the
configuration states; the arithmetic of each step is float32.  ``quant``
rounds the operands of every matrix product (the lower-precision control);
the default leaves them alone.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .clients import client_sizes, token_rows
from .spec import family_of
from .weights import change_norms, init_params

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32

# Stream tags of the cohort draw and of the per-epoch reshuffle.
_TAG_COHORT, _TAG_RR = 0xC0407, 0xA11CE


def _rng(*keys: int) -> np.random.Generator:
    seq = np.random.SeedSequence(entropy=[int(k) & 0xFFFFFFFF for k in keys])
    return np.random.default_rng(seq)


# ---------------------------------------------------------------------------
# Traffic
# ---------------------------------------------------------------------------


class ClientWork(NamedTuple):
    client: int
    tokens: np.ndarray   # [K_i, B, seq_len + 1] int32
    eta: float           # local step size local_lr / K_i
    coeff: float         # aggregation coefficient w_i / p_i


SUPPORTED = {"sampling": ("uniform",), "algorithm": ("fedshuffle",),
             "server_opt": ("sgd",),
             "cohort_mode": ("sequential", "vmapped"), "uplink": ("identity",)}


def check_traffic(fl: dict) -> None:
    for key, allowed in SUPPORTED.items():
        if fl.get(key, allowed[0]) not in allowed:
            raise NotImplementedError(f"the reference covers {key} in {allowed}, "
                                      f"not {fl[key]!r}")
    if fl.get("epochs_max", 0) > fl["epochs"] or not fl.get("reshuffle", True):
        raise NotImplementedError("the reference covers fixed epochs with reshuffling")


def round_plan(traffic: dict, vocab: int, task_seed: int, rnd: int) -> list[ClientWork]:
    fl = traffic["fl"]
    check_traffic(fl)
    n, C, B = fl["num_clients"], fl["cohort_size"], fl["local_batch"]
    sizes = client_sizes(traffic["clients"], n)
    ids = _rng(fl["seed"], _TAG_COHORT, rnd).choice(n, size=C, replace=False)
    weights = sizes / sizes.sum()
    out = []
    for cid in ids:
        cid = int(cid)
        n_i, spe = int(sizes[cid]), max(1, -(-int(sizes[cid]) // B))
        rows = []
        for e in range(fl["epochs"]):
            order = np.resize(_rng(fl["seed"], _TAG_RR, cid, rnd, e).permutation(n_i), spe * B)
            rows.extend(order[s * B:(s + 1) * B] for s in range(spe))
        k_i = len(rows)
        toks = token_rows(task_seed, cid, np.stack(rows), vocab, traffic["seq_len"])
        out.append(ClientWork(cid, toks.reshape(k_i, B, -1),
                              fl["local_lr"] / k_i, float(weights[cid] / (C / n))))
    return out


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------


def no_quant(x):
    return x


def fp8_quant(x):
    """Round to float8_e4m3fn under a per-tensor scale; the gradient passes
    straight through (the operands of the backward products stay rounded)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    return x + jax.lax.stop_gradient(q - x)


def mm(quant, a, b):
    return jnp.matmul(quant(a), quant(b), precision=HIGHEST)


def rmsnorm(scale, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _f32(tree):
    return jax.tree.map(lambda t: t.astype(F32), tree)


def _layer(stack, l):
    return jax.tree.map(lambda t: jax.lax.dynamic_index_in_dim(t, l, keepdims=False), stack)


def _subtree(tree: dict, path: str):
    for k in path.split("/"):
        tree = tree[k]
    return tree


def _replaced(tree: dict, path: str, value) -> dict:
    """A copy of ``tree`` with the subtree at ``path`` replaced."""
    head, _, rest = path.partition("/")
    return {**tree, head: _replaced(tree[head], rest, value) if rest else value}


@partial(jax.jit, static_argnums=(0, 1, 2))
def _layer_fwd(s, quant, fn, stack, l, h):
    return fn(s, quant, _f32(_layer(stack, l)), h)


@partial(jax.jit, static_argnums=(0, 1))
def _head_grad(s, quant, h, norm_scale, head, labels):
    """Loss and its gradients w.r.t. the last hidden state, the final norm
    and the output matrix ([V, D] when tied, [D, V] otherwise)."""

    def loss(h, ns, w):
        x = rmsnorm(ns, h, s.norm_eps)
        logits = mm(quant, x, w.T if s.tied else w)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(lse - picked)

    val, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(h, norm_scale.astype(F32),
                                                             head.astype(F32))
    return val, grads


@partial(jax.jit, static_argnums=(0, 1, 2), donate_argnums=(3,))
def _layer_bwd_update(s, quant, fn, stack, l, h_in, dh, eta):
    """Backward through layer ``l`` of ``stack`` with cotangent (dh, 1): the
    layer's auxiliary loss enters the loss with weight 1."""
    lp = _layer(stack, l)
    _, vjp = jax.vjp(lambda p, h: fn(s, quant, p, h), _f32(lp), h_in)
    dp, dh_in = vjp((dh, jnp.ones((), F32)))
    new = jax.tree.map(lambda w, g: (w.astype(F32) - eta * g).astype(w.dtype), lp, dp)
    stack = jax.tree.map(lambda t, n: jax.lax.dynamic_update_index_in_dim(t, n, l, 0),
                         stack, new)
    return stack, dh_in


@partial(jax.jit, donate_argnums=(0,))
def _sgd(w, g, eta):
    return (w.astype(F32) - eta * g).astype(w.dtype)


@partial(jax.jit, donate_argnums=(0,))
def _embed_update(embed, g_head, inputs, dh0, eta):
    g = g_head.at[inputs.reshape(-1)].add(dh0.reshape(-1, dh0.shape[-1]))
    return (embed.astype(F32) - eta * g).astype(embed.dtype)


@jax.jit
def _embed_lookup(embed, inputs):
    return embed[inputs].astype(F32)


def local_step(s, params: dict, tokens, eta: float,
               quant: Callable = no_quant) -> tuple[dict, float]:
    """One SGD step on ``tokens`` [B, seq_len + 1]; returns the new params
    (``params`` is consumed) and the step's loss: mean next-token cross
    entropy plus the layers' auxiliary losses."""
    tokens = jnp.asarray(tokens)
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    eta = jnp.float32(eta)
    order = family_of(s).layers(s)
    stacks = {path: _subtree(params, path) for path, _, _ in order}
    h = _embed_lookup(params["embed"], inputs)
    saved, aux = [], jnp.float32(0.0)
    for path, l, fn in order:
        saved.append(h)
        h, a = _layer_fwd(s, quant, fn, stacks[path], jnp.int32(l), h)
        aux = aux + a
    head = params["embed"] if s.tied else params["lm_head"]
    loss, (dh, d_norm, d_head) = _head_grad(s, quant, h, params["final_norm"]["scale"],
                                            head, labels)
    for (path, l, fn), h_in in zip(reversed(order), reversed(saved)):
        stacks[path], dh = _layer_bwd_update(s, quant, fn, stacks[path], jnp.int32(l),
                                             h_in, dh, eta)
    out = params
    for path, stack in stacks.items():
        out = _replaced(out, path, stack)
    out = {**out, "final_norm": {"scale": _sgd(params["final_norm"]["scale"], d_norm, eta)}}
    if s.tied:
        out["embed"] = _embed_update(params["embed"], d_head, inputs, dh, eta)
    else:
        out["lm_head"] = _sgd(params["lm_head"], d_head, eta)
        out["embed"] = _embed_update(params["embed"], jnp.zeros(params["embed"].shape, F32),
                                     inputs, dh, eta)
    return out, float(loss + aux)


@jax.jit
def _copy(tree):
    return jax.tree.map(jnp.copy, tree)


@partial(jax.jit, donate_argnums=(0,))
def _accumulate(acc, y, x, coeff):
    return jax.tree.map(lambda a, yl, xl: a + coeff * (yl.astype(F32) - xl.astype(F32)),
                        acc, y, x)


@jax.jit
def _zeros_f32(tree):
    return jax.tree.map(lambda t: jnp.zeros(t.shape, F32), tree)


@partial(jax.jit, donate_argnums=(0,))
def _server(x, acc, lr):
    return jax.tree.map(lambda xl, a: (xl.astype(F32) + lr * a).astype(xl.dtype), x, acc)


class Readings(NamedTuple):
    """What the comparison reads from a run of three rounds."""

    losses: list            # each round's mean client loss
    grad_norms: dict        # leaf -> ||x_1 - x_0||  (the first pseudo-gradient)
    change_norms: dict      # leaf -> ||x_R - x_0||
    inputs: list            # per round: [(client, tokens [K_i, B, T+1])]


def reference_rounds(s, traffic: dict, seed: int, task_seed: int,
                     rounds: int, quant: Callable = no_quant,
                     rows: int | None = None) -> Readings:
    """Run ``rounds`` FedShuffle rounds from the weights of ``seed``.

    ``rows`` keeps only the first rows of every local batch (a planted fault
    for the comparison's tests)."""
    server_lr = float(traffic["fl"].get("server_lr", 1.0))
    x = init_params(s, seed)
    losses, inputs, grad_norms = [], [], None
    for r in range(rounds):
        plan = round_plan(traffic, s.vocab, task_seed, r)
        inputs.append([(w.client, w.tokens) for w in plan])
        acc = _zeros_f32(x)
        client_losses = []
        for w in plan:
            y = _copy(x)
            step_losses = []
            for k in range(len(w.tokens)):
                batch = w.tokens[k] if rows is None else w.tokens[k][:rows]
                y, loss = local_step(s, y, batch, w.eta, quant)
                step_losses.append(loss)
            client_losses.append(float(np.mean(step_losses)))
            acc = _accumulate(acc, y, x, jnp.float32(w.coeff))
        x = _server(x, acc, jnp.float32(server_lr))
        losses.append(float(np.mean(client_losses)))
        if r == 0:
            grad_norms = change_norms(s, x, seed)
    return Readings(losses, grad_norms, change_norms(s, x, seed), inputs)
