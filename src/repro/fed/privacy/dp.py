"""DP-FedShuffle mechanism: per-client L2 clipping + server Gaussian noise.

The mechanism is the standard DP-FedAvg recipe (McMahan et al. 2018) adapted
to FedShuffle's weight-aware aggregation:

* every *shipped* client update is clipped to L2 norm ``fl.dp_clip`` — an
  exact per-client sensitivity bound, applied to the final delta (not per
  step, which is what ``local_clip``/``fl.clip_norm`` does — the two are
  rejected together at bind time precisely because their bounds would
  silently stack);
* the server adds isotropic Gaussian noise to the weighted aggregate with

      sigma = fl.dp_noise_mult * fl.dp_clip * max_i |coeff_i|

  where ``coeff_i`` are the strategy's bound FedShuffle aggregation
  coefficients (``valid_i * w_i / q_i``, staleness-discounted when
  buffered).  ``dp_clip * max|coeff|`` bounds the L2 distance the aggregate
  can move when one client's data changes, so ``dp_noise_mult`` is the
  classic noise multiplier ``z`` the RDP accountant consumes.

Noise is *counter-based*: drawn per ``(seed, round)`` off the rr_perm hash
chain (``TAG_PRIVACY`` / ``SUB_DP_NOISE``, registry in ``utils/tags.py``)
via Box–Muller over two ``fmix32`` uniform streams.  No PRNG state exists
anywhere, so the legacy loop, the cohort engine, the prefetch thread, and a
checkpoint-resumed run replay bitwise-identical noise for the same round.

Clipping is exposed twice on purpose:

* :func:`dp_clip_cohort` — the round driver's path: clips the slot-order
  ``[C]`` delta stack and returns the exact per-slot clipped indicator
  (feeding the ``dp_clipped_frac`` metric, which post-hoc norms cannot
  recover exactly);
* ``"dp_clip"`` in the ClientTransform registry — a ``finalize_delta``
  chain link computing the same function per client, so custom local-update
  chains can opt into DP clipping explicitly and tests can pin the two
  paths bitwise-equal.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...core.local import ClientTransform, register_client_transform
from ...kernels.rr_perm.ref import fmix32, key_combine, stream_key
from ...utils.tags import SUB_DP_NOISE, TAG_PRIVACY

_EPS = 1e-12  # clip-scale denominator guard (matches local_clip's)


def clip_update(delta, clip: float):
    """L2-clip one client's update tree to norm ``clip``.

    Returns ``(clipped delta, was_clipped {0.,1.}, scale)`` — norm and scale
    computed in fp32 regardless of leaf dtype.
    """
    sq = sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
             for x in jax.tree.leaves(delta))
    nrm = jnp.sqrt(sq)
    scale = jnp.minimum(jnp.float32(1.0),
                        jnp.float32(clip) / jnp.maximum(nrm, _EPS))
    out = jax.tree.map(
        lambda x: (x.astype(jnp.float32) * scale).astype(x.dtype), delta)
    return out, (nrm > clip).astype(jnp.float32), scale


def dp_clip_cohort(deltas, fl):
    """Clip a slot-order ``[C]`` delta stack to ``fl.dp_clip`` per slot.

    Same math as :func:`clip_update` vectorized over the leading axis.
    Returns ``(clipped stack, clipped indicator [C], scale [C])``.
    """
    clip = jnp.float32(fl.dp_clip)
    sq = sum(jnp.sum(jnp.square(x.astype(jnp.float32)),
                     axis=tuple(range(1, x.ndim)))
             for x in jax.tree.leaves(deltas))
    nrm = jnp.sqrt(sq)                                   # [C]
    scale = jnp.minimum(jnp.float32(1.0), clip / jnp.maximum(nrm, _EPS))

    def sc(x):
        s = scale.reshape((-1,) + (1,) * (x.ndim - 1))
        return (x.astype(jnp.float32) * s).astype(x.dtype)

    return (jax.tree.map(sc, deltas), (nrm > clip).astype(jnp.float32), scale)


def dp_clip_transform(loss_fn, fl) -> ClientTransform:
    """``"dp_clip"`` chain link: clip the *shipped* update to ``fl.dp_clip``
    (a ``finalize_delta`` hook — per-step directions are untouched)."""
    limit = float(fl.dp_clip)
    if not limit > 0:
        raise ValueError(
            f"client transform 'dp_clip' needs fl.dp_clip > 0 (the per-update "
            f"L2 sensitivity bound), got {limit!r}")

    def finalize_delta(end, delta):
        return clip_update(delta, limit)[0]

    return ClientTransform(name="dp_clip", init=lambda params: {},
                           update=lambda step, d, carry, cstate: (d, carry),
                           finalize_delta=finalize_delta)


register_client_transform("dp_clip", dp_clip_transform)


def noise_key(seed: int, rnd, xp=jnp):
    """The round's DP-noise stream key — ``[1]`` uint32, per (seed, round)."""
    dt = xp.uint32
    base = stream_key(seed, dt(0), xp.asarray(rnd).astype(dt), xp)
    key = key_combine(base, dt(TAG_PRIVACY), xp)
    return key_combine(key, dt(SUB_DP_NOISE), xp)


def _std_normal(key, shape):
    """Counter-based standard normals: Box–Muller over two fmix32 uniform
    streams (element counter ``j`` and its ``key_combine(. , 1)`` branch)."""
    n = max(1, int(np.prod(shape, dtype=np.int64)))
    ctr = jnp.arange(n, dtype=jnp.uint32)
    ka = key_combine(key.reshape(1), ctr, jnp)           # [n]
    kb = key_combine(ka, jnp.uint32(1), jnp)
    # (u + 0.5) / 2^32 lands strictly inside (0, 1): log/cos stay finite
    u1 = (fmix32(ka, jnp).astype(jnp.float32) + 0.5) * jnp.float32(2.0 ** -32)
    u2 = (fmix32(kb, jnp).astype(jnp.float32) + 0.5) * jnp.float32(2.0 ** -32)
    z = jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(jnp.float32(2.0 * np.pi) * u2)
    return z.reshape(shape)


def add_dp_noise(delta_agg, coeff, valid, fl, rnd):
    """Add the round's Gaussian noise to the aggregated update (in-jit).

    ``sigma = dp_noise_mult * dp_clip * max_i(valid_i * |coeff_i|)`` — the
    exact L2 sensitivity of the weighted sum under per-client clipping.
    Returns ``(noisy aggregate, sigma)``.
    """
    sens = jnp.float32(fl.dp_clip) * jnp.max(
        valid.astype(jnp.float32) * jnp.abs(coeff.astype(jnp.float32)))
    sigma = jnp.float32(fl.dp_noise_mult) * sens
    key = noise_key(fl.seed, rnd)
    leaves, treedef = jax.tree.flatten(delta_agg)
    out = []
    for i, leaf in enumerate(leaves):
        z = _std_normal(key_combine(key, jnp.uint32(i), jnp), leaf.shape)
        out.append((leaf.astype(jnp.float32) + sigma * z).astype(leaf.dtype))
    return jax.tree.unflatten(treedef, out), sigma


def dp_metrics(clipped, sigma, valid, valid_sum) -> dict:
    """The DP round keys: the share of valid clients the clip bit (the exact
    indicator from :func:`dp_clip_cohort`, not a post-hoc norm test) and the
    noise's sigma; ``valid_sum`` is the cohort's clamped valid count."""
    return {"dp_clipped_frac": (clipped * valid).sum() / valid_sum,
            "dp_sigma": sigma}
