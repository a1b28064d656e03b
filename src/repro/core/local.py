"""Local client work: composable per-step transforms over masked RR epochs.

The non-identical-local-steps regime (different |D_i|, E_i) is carried by a
static ``lax.scan`` over ``K_max`` steps with a per-step {0,1} mask — a masked
step is an exact no-op, so the semantics match the paper's variable-length
loops while shapes stay static for XLA.  Every producer of the mask pads at
the end, so a client run alone stops at its last unmasked step instead
(:func:`scan_to_last_step`, a traced trip count over the same shapes).

Step-size convention (Algorithm 4): client i uses ``eta_l / c_i`` per local
step, where the algorithm chooses ``c_i`` (FedShuffle: c_i = K_i, the number
of local steps; FedAvg/FedNova: c_i = 1).

**Client-transform chains.**  A local update rule is an optax-style chain of
:class:`ClientTransform` links.  Every local step computes the fp32 gradient
direction ``d = g(y)`` and threads it through the chain; the driver then
applies the canonical masked descent ``y <- (y - eta*m*d).astype(dtype)``.
A transform may keep

* **per-round carry state** (``init``/``update``) — reset at every round,
  e.g. a local momentum buffer.  Carry updates on masked steps are discarded
  by the runner (``jnp.where`` select), so masked steps stay exact no-ops.
* **persistent per-client state** (``client_init``/``finalize``) — e.g.
  SCAFFOLD control variates.  The round driver stores one ``[N+1, ...]``
  *state bank* per stateful transform on ``ServerState.clients`` (row ``N``
  is scratch for invalid cohort padding), gathers the cohort's rows inside
  the jitted round step, and slot-order scatters the finalized rows back —
  O(cohort) state traffic per round, independent of population size.

``local_sgd`` / ``local_mvr`` below are the original monolithic rules, kept
verbatim as the frozen bitwise references: the empty chain and the
``("mvr",)`` chain reproduce them bit-for-bit (equivalence suites assert it).
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from ..utils.pytree import tree_sub, tree_zeros_like


# ---------------------------------------------------------------------------
# Frozen monolithic references (the pre-chain implementations).  These are
# the bitwise ground truth the chain runner is held to — do not "refactor"
# them to share code with the chains.
# ---------------------------------------------------------------------------


def local_sgd(loss_fn: Callable, params, data, step_mask, lr):
    """RR-epoch local SGD (reference; the empty chain reproduces it).

    loss_fn(params, microbatch) -> (scalar, metrics-dict)
    data: pytree, leaves [K_max, B, ...]; step_mask [K_max]; lr scalar
    (already eta_l / c_i).  Returns (delta = y - x, mean masked loss).
    """
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def step(y, xs):
        mb, m = xs
        (l, _), g = grad_fn(y, mb)
        with jax.named_scope("local_apply"):
            y = jax.tree.map(
                lambda a, b: (a.astype(jnp.float32) - (lr * m) * b.astype(jnp.float32)).astype(a.dtype),
                y, g,
            )
        return y, l * m

    with jax.named_scope("local_step"):
        y, losses = jax.lax.scan(step, params, (data, step_mask))
    denom = jnp.maximum(step_mask.sum(), 1.0)
    with jax.named_scope("client_delta"):
        delta = tree_sub(y, params)
    return delta, losses.sum() / denom


def local_mvr(loss_fn: Callable, params, momentum, data, step_mask, lr, a):
    """MVR-corrected local steps (reference; the ("mvr",) chain reproduces it).

    Paper eq. 12-13:

    d_{i,e,j} = a*g(y) + (1-a)*m + (1-a)*(g(y) - g(x))
              = g(y) + (1-a)*(m - g(x))
    where g(.) is the gradient of the *same* RR sample at the local iterate y
    and at the round-start point x.  Two gradient passes per step; the
    reported loss rides along with the g(y) pass (pre-update, same convention
    as :func:`local_sgd`) instead of costing a third forward pass.
    """
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    gx_fn = jax.grad(lambda p, mb: loss_fn(p, mb)[0])

    def step(y, xs):
        mb, m = xs
        (l, _), gy = grad_fn(y, mb)
        gx = gx_fn(params, mb)
        d = jax.tree.map(
            lambda gyl, gxl, ml: gyl.astype(jnp.float32) + (1.0 - a)
            * (ml.astype(jnp.float32) - gxl.astype(jnp.float32)),
            gy, gx, momentum,
        )
        with jax.named_scope("local_apply"):
            y = jax.tree.map(
                lambda p, dl: (p.astype(jnp.float32) - (lr * m) * dl).astype(p.dtype), y, d
            )
        return y, l * m

    with jax.named_scope("local_step"):
        y, losses = jax.lax.scan(step, params, (data, step_mask))
    denom = jnp.maximum(step_mask.sum(), 1.0)
    with jax.named_scope("client_delta"):
        delta = tree_sub(y, params)
    return delta, losses.sum() / denom


# ---------------------------------------------------------------------------
# ClientTransform chains — the composable local-update API
# ---------------------------------------------------------------------------


class StepCtx(NamedTuple):
    """What one local step exposes to the transform chain (all traced).

    ``x`` is the round-start point, ``y`` the current local iterate, ``mb``
    the step's microbatch, ``mask`` the step's {0,1} validity, ``eta`` the
    client's step size (already ``eta_l * lr_mult / c_i``), ``momentum`` the
    server momentum tree the round handed down (zeros when the server opt
    keeps none), ``opt`` the full server opt-state dict (broadcast, read-only
    — declare the keys a transform reads via ``ClientTransform.needs`` so
    binding validates the pairing), ``loss``/``grad`` the value-and-grad of
    the loss at ``y`` on ``mb``.
    """

    x: Any
    y: Any
    mb: Any
    mask: Any
    eta: Any
    momentum: Any
    opt: Any
    loss: Any
    grad: Any


class RoundEnd(NamedTuple):
    """Round-end context for ``finalize`` (per client): the round-start point
    ``x``, final iterate ``y``, ``delta = y - x``, realized step count
    ``steps`` (= mask.sum(); clamp before dividing — invalid padding slots
    have 0), the step size ``eta``, and the server ``momentum``/``opt``."""

    x: Any
    y: Any
    delta: Any
    steps: Any
    eta: Any
    momentum: Any
    opt: Any


class ClientTransform(NamedTuple):
    """One link of a local-update chain (all hooks pure pytree functions).

    ``init(params) -> carry`` builds the per-round carry (``{}`` if none);
    ``update(step: StepCtx, d, carry, cstate) -> (d', carry')`` maps the fp32
    descent direction (``cstate`` is the client's persistent slice, or None
    for stateless transforms).  Optional persistent per-client state:
    ``client_init(params)`` returns one client's state template (the round
    driver banks it ``[N+1, ...]`` on ``ServerState.clients``) and
    ``finalize(end: RoundEnd, carry, cstate) -> cstate'`` commits the round's
    update.  ``finalize_delta(end: RoundEnd, delta) -> delta'`` rewrites the
    *shipped* update after the local steps finish (e.g. the privacy plane's
    per-client DP clip); ``end.delta`` stays the raw local delta, hooks apply
    in chain order, and a chain with no ``finalize_delta`` hooks adds zero
    ops (the bitwise off-contract).  ``needs`` lists server opt-state keys
    the transform reads (``bind_strategy`` refuses server opts that do not
    provide them).
    """

    name: str
    init: Callable
    update: Callable
    client_init: Callable | None = None
    finalize: Callable | None = None
    needs: tuple = ()
    finalize_delta: Callable | None = None


class ClientChain(NamedTuple):
    """A declared local-update rule: a named composition of transforms.

    ``transforms`` holds registry names (resolved through
    :data:`CLIENT_TRANSFORMS` at bind time) and/or factory callables
    ``make(loss_fn, fl) -> ClientTransform``.  The empty chain is plain
    RR-SGD.
    """

    name: str
    transforms: tuple = ()


# name -> make(loss_fn, fl) -> ClientTransform
CLIENT_TRANSFORMS: dict[str, Callable] = {}


def register_client_transform(name: str, make: Callable, *,
                              overwrite: bool = False) -> None:
    """Register ``make(loss_fn, fl) -> ClientTransform`` under ``name``."""
    if not overwrite and name in CLIENT_TRANSFORMS:
        raise ValueError(
            f"client transform {name!r} already registered (pass overwrite=True to replace)")
    CLIENT_TRANSFORMS[name] = make


def resolve_chain(chain: ClientChain, loss_fn: Callable, fl) -> tuple:
    """Instantiate a chain's transforms against (loss_fn, fl)."""
    out = []
    for t in chain.transforms:
        if isinstance(t, str):
            if t not in CLIENT_TRANSFORMS:
                raise ValueError(
                    f"local update {chain.name!r}: unknown client transform "
                    f"{t!r}; have {sorted(CLIENT_TRANSFORMS)}")
            t = CLIENT_TRANSFORMS[t]
        out.append(t(loss_fn, fl))
    names = [t.name for t in out if t.client_init is not None]
    if len(names) != len(set(names)):
        raise ValueError(
            f"local update {chain.name!r}: stateful transforms must have "
            f"unique names (the name keys the client state bank), got {names}")
    return tuple(out)


def chain_client_template(transforms: tuple) -> Callable | None:
    """``params -> {transform name: one client's persistent state}`` for the
    stateful links of a resolved chain, or None when the chain is stateless."""
    stateful = [t for t in transforms if t.client_init is not None]
    if not stateful:
        return None

    def template(params):
        return {t.name: t.client_init(params) for t in stateful}

    return template


def scan_to_last_step(step: Callable, carry, data, step_mask):
    """``lax.scan(step, carry, (data, step_mask))`` for a ``step`` that is an
    exact no-op on a masked step, run only to the last unmasked one.

    The trip count is traced: 1 + the last index with mask > 0, or 0 when
    every step is masked, so one compiled loop serves every mask.  Each step
    reads microbatch ``k`` by dynamic index, as the scan does, and writes its
    output into a zero-initialised ``[K_max]`` buffer, so the skipped tail
    reads 0 where the scan would have written ``l * 0``.  Masked steps before
    the last unmasked one still run, and stay no-ops through ``step``'s own
    masking.
    """
    k_max = step_mask.shape[0]
    n = jnp.max(jnp.where(step_mask > 0, jnp.arange(1, k_max + 1), 0))
    ys0 = jnp.zeros((k_max,), jnp.promote_types(step_mask.dtype, jnp.float32))

    def body(k, state):
        carry, ys = state
        mb = jax.tree.map(lambda t: jax.lax.dynamic_index_in_dim(t, k, keepdims=False),
                          data)
        carry, y = step(carry, (mb, step_mask[k]))
        return carry, ys.at[k].set(y.astype(ys.dtype))

    # the jit gives the loop a function of its own: a while loop's constants
    # are hoisted to the top of the enclosing function and carry its scopes,
    # which would take the loop body's ops out of the caller's named scope
    # (``local_step``) on the profile; the compiled ops are the same
    return jax.jit(lambda n, init: jax.lax.fori_loop(0, n, body, init))(n, (carry, ys0))


def build_local_step(transforms: tuple, loss_fn: Callable, *,
                     trim_padding: bool = False) -> Callable:
    """Compile a resolved transform chain into the per-client local update

        one_client(params, momentum, opt, data, step_mask, eta, cstate)
            -> (delta, loss, cstate')

    For the empty chain this is bitwise-identical to :func:`local_sgd`; for
    the ``mvr`` transform, to :func:`local_mvr` (the equivalence suites hold
    both).  ``cstate`` maps stateful-transform names to that client's
    persistent slice (pass ``{}`` for stateless chains).

    ``trim_padding`` runs the local steps through :func:`scan_to_last_step`,
    so a client's tail of masked steps is never computed; the results are
    bitwise those of the fixed ``K_max``-step scan.  It is for clients run
    one at a time: under ``vmap`` a data-dependent loop runs to the cohort's
    longest client and adds selects, so vmapped cohorts keep the scan.
    """
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    stateful = tuple(t for t in transforms if t.client_init is not None)

    def one_client(params, momentum, opt, data, step_mask, eta, cstate):
        def step(carry, xs):
            y, carries = carry
            mb, m = xs
            (l, _), g = grad_fn(y, mb)
            d = jax.tree.map(lambda t: t.astype(jnp.float32), g)
            sctx = StepCtx(x=params, y=y, mb=mb, mask=m, eta=eta,
                           momentum=momentum, opt=opt, loss=l, grad=g)
            new_carries = []
            for t, c in zip(transforms, carries):
                cs = cstate.get(t.name) if t.client_init is not None else None
                d, c_new = t.update(sctx, d, c, cs)
                # a masked step must be an exact no-op for carry state too
                new_carries.append(jax.tree.map(
                    lambda n, o: jnp.where(m > 0, n, o), c_new, c))
            with jax.named_scope("local_apply"):
                y = jax.tree.map(
                    lambda p, dl: (p.astype(jnp.float32) - (eta * m) * dl).astype(p.dtype),
                    y, d,
                )
            return (y, tuple(new_carries)), l * m

        carries0 = tuple(t.init(params) for t in transforms)
        with jax.named_scope("local_step"):
            if trim_padding:
                (y, carries), losses = scan_to_last_step(
                    step, (params, carries0), data, step_mask)
            else:
                (y, carries), losses = jax.lax.scan(step, (params, carries0),
                                                    (data, step_mask))
        denom = jnp.maximum(step_mask.sum(), 1.0)
        with jax.named_scope("client_delta"):
            delta = tree_sub(y, params)
        new_cstate = cstate
        shippers = tuple(t for t in transforms if t.finalize_delta is not None)
        end = None
        if stateful or shippers:
            end = RoundEnd(x=params, y=y, delta=delta, steps=step_mask.sum(),
                           eta=eta, momentum=momentum, opt=opt)
        if stateful:
            new_cstate = dict(cstate)
            for t, c in zip(transforms, carries):
                if t.client_init is not None:
                    new_cstate[t.name] = t.finalize(end, c, cstate[t.name])
        for t in shippers:
            delta = t.finalize_delta(end, delta)
        return delta, losses.sum() / denom, new_cstate

    return one_client


# ---------------------------------------------------------------------------
# Built-in transforms (factories: make(loss_fn, fl) -> ClientTransform)
# ---------------------------------------------------------------------------


def mvr_transform(loss_fn: Callable, fl) -> ClientTransform:
    """MVR-corrected direction (paper eq. 12-13):
    ``d' = d + (1-a) * (m - g(x))`` with ``g(x)`` the same RR sample's
    gradient at the round-start point.  Needs a server *gradient estimate* in
    ``opt['m']`` — declared as the semantic tag ``grad_estimate`` so only the
    ``mvr`` server opt satisfies it (heavy-ball's ``m`` is a momentum of
    aggregated deltas, a different quantity at a different scale; matching on
    the raw key name would silently consume it)."""
    gx_fn = jax.grad(lambda p, mb: loss_fn(p, mb)[0])
    a = fl.mvr_a

    def update(step: StepCtx, d, carry, cstate):
        gx = gx_fn(step.x, step.mb)
        d = jax.tree.map(
            lambda dl, gxl, ml: dl + (1.0 - a)
            * (ml.astype(jnp.float32) - gxl.astype(jnp.float32)),
            d, gx, step.momentum,
        )
        return d, carry

    return ClientTransform(name="mvr", init=lambda params: {}, update=update,
                           needs=("grad_estimate",))


def scaffold_transform(loss_fn: Callable, fl) -> ClientTransform:
    """SCAFFOLD control variates under client sampling (Karimireddy et al.
    2020; the 5th-generation local-training regime of Grudzień et al. 2022).

    Per step: ``d' = d + (c - c_i)`` with ``c_i`` the client's persistent
    control variate (state bank) and ``c = opt['c']`` the server's.  At round
    end (option II): ``c_i+ = c_i - c + (x - y)/(K_i * eta_i)``.  The paired
    ``scaffold`` server opt maintains ``c`` from the cohort's ``c_i`` deltas
    with w/p importance debiasing — O(cohort) work per round."""

    def client_init(params):
        return {"c": tree_zeros_like(params)}

    def update(step: StepCtx, d, carry, cstate):
        d = jax.tree.map(
            lambda dl, ci, cg: dl + (cg.astype(jnp.float32)
                                     - ci.astype(jnp.float32)),
            d, cstate["c"], step.opt["c"],
        )
        return d, carry

    def finalize(end: RoundEnd, carry, cstate):
        k = jnp.maximum(end.steps, 1.0)
        # c_i+ = c_i - c + (x - y)/(K eta)  and  x - y = -delta
        return {"c": jax.tree.map(
            lambda ci, cg, dl: (ci.astype(jnp.float32) - cg.astype(jnp.float32)
                                - dl.astype(jnp.float32) / (k * end.eta)
                                ).astype(ci.dtype),
            cstate["c"], end.opt["c"], end.delta,
        )}

    return ClientTransform(name="scaffold", init=lambda params: {},
                           update=update, client_init=client_init,
                           finalize=finalize, needs=("c",))


def prox_transform(loss_fn: Callable, fl) -> ClientTransform:
    """FedProx proximal term (Li et al. 2020): ``d' = d + mu * (y - x)``."""
    mu = fl.prox_mu
    if not mu > 0:
        raise ValueError(
            f"local update 'fedprox' needs fl.prox_mu > 0 (the proximal "
            f"coefficient), got {mu!r}")

    def update(step: StepCtx, d, carry, cstate):
        d = jax.tree.map(
            lambda dl, yl, xl: dl + mu * (yl.astype(jnp.float32)
                                          - xl.astype(jnp.float32)),
            d, step.y, step.x,
        )
        return d, carry

    return ClientTransform(name="prox", init=lambda params: {}, update=update)


def clip_transform(loss_fn: Callable, fl) -> ClientTransform:
    """Per-step global-norm clip of the descent direction to
    ``fl.clip_norm`` — composable after any direction-producing transform."""
    limit = fl.clip_norm
    if not limit > 0:
        raise ValueError(
            f"local update 'local_clip' needs fl.clip_norm > 0 (the per-step "
            f"direction-norm bound), got {limit!r}")

    def update(step: StepCtx, d, carry, cstate):
        nrm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(d)))
        scale = jnp.minimum(1.0, limit / jnp.maximum(nrm, 1e-12))
        return jax.tree.map(lambda x: x * scale, d), carry

    return ClientTransform(name="clip", init=lambda params: {}, update=update)


for _name, _make in (("mvr", mvr_transform), ("scaffold", scaffold_transform),
                     ("prox", prox_transform), ("clip", clip_transform)):
    register_client_transform(_name, _make)


def full_local_gradient(loss_fn: Callable, params, data, step_mask):
    """Masked-mean gradient over the client's local data (one unbiased pass
    per epoch; across the whole RR stream the mean equals grad f_i up to the
    wrap padding of partial batches).  Used by exact FedShuffleMVR (eq. 14)."""
    grad_fn = jax.grad(lambda p, mb: loss_fn(p, mb)[0])

    def step(acc, xs):
        mb, m = xs
        g = grad_fn(params, mb)
        acc = jax.tree.map(lambda A, G: A + m * G.astype(A.dtype), acc, g)
        return acc, None

    zeros = jax.tree.map(lambda x: jnp.zeros_like(x, dtype=jnp.float32), params)
    acc, _ = jax.lax.scan(step, zeros, (data, step_mask))
    denom = jnp.maximum(step_mask.sum(), 1.0)
    return jax.tree.map(lambda A: A / denom, acc)
