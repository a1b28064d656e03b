"""Shared layers: norms, RoPE, MLPs, inits.  Pure JAX, no flax.

Parameter convention: plain nested dicts of ``jnp.ndarray``; every layer is an
``init(key, ...) -> params`` plus a pure ``apply(params, x, ...)`` pair.
Per-layer parameters are *stacked along a leading layer axis* by the model
builders so the forward pass is a ``lax.scan`` over layers (compact HLO even
for 80-layer configs).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def dense_init(key, in_dim: int, out_dim: int, dtype, scale: float | None = None):
    scale = scale if scale is not None else (1.0 / jnp.sqrt(in_dim))
    return (jax.random.normal(key, (in_dim, out_dim)) * scale).astype(dtype)


def embed_init(key, vocab: int, dim: int, dtype):
    return (jax.random.normal(key, (vocab, dim)) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_init(dim: int, dtype):
    return {"scale": jnp.ones((dim,), dtype=dtype)}


def rmsnorm(params, x, eps: float = 1e-5):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# RoPE: "full" (llama-style over all head dims), "half" (ChatGLM 2d: rotate
# only the first half of head dims), "none".
# ---------------------------------------------------------------------------


def rope_frequencies(dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention scaling 0.1 * mscale * ln(factor) + 1 (1 at factor <= 1)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(dim: int, theta: float, yarn) -> np.ndarray:
    """DeepSeek-V2's YaRN inverse frequencies [dim/2] (float32).

    Dimension pairs below the correction range keep the rope frequency
    theta^(-2i/dim) (extrapolation), those above it take that frequency over
    ``yarn.factor`` (interpolation), and a linear ramp blends the two in
    between.  The range is [floor(d(beta_fast)), ceil(d(beta_slow))] with
    d(r) = dim * ln(original_max / (2 pi r)) / (2 ln theta)."""
    def corr(rot):
        return dim * math.log(yarn.original_max / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr(yarn.beta_fast)), 0)
    high = min(math.ceil(corr(yarn.beta_slow)), dim - 1)
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low if high > low else 0.001), 0.0, 1.0)
    return (extra / yarn.factor * ramp + extra * (1.0 - ramp)).astype(np.float32)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float, kind: str = "full",
               inv_freq=None):
    """x [..., T, n_heads, head_dim]; positions [..., T] (absolute);
    ``inv_freq`` [rot/2] replaces the plain rope frequencies (YaRN)."""
    if kind == "none":
        return x
    hd = x.shape[-1]
    rot_dim = hd if kind == "full" else hd // 2
    if inv_freq is None:
        freqs = rope_frequencies(rot_dim, theta)                    # [rot/2]
    else:
        freqs = jnp.asarray(inv_freq, jnp.float32)
    ang = positions[..., :, None].astype(jnp.float32) * freqs       # [..., T, rot/2]
    cos = jnp.cos(ang)[..., :, None, :]                             # [..., T, 1, rot/2]
    sin = jnp.sin(ang)[..., :, None, :]
    xr = x[..., :rot_dim].astype(jnp.float32)
    x1, x2 = xr[..., : rot_dim // 2], xr[..., rot_dim // 2 :]
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    out = jnp.concatenate([rotated.astype(x.dtype), x[..., rot_dim:]], axis=-1)
    return out


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def swiglu_init(key, d_model: int, d_ff: int, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "gate": dense_init(k1, d_model, d_ff, dtype),
        "up": dense_init(k2, d_model, d_ff, dtype),
        "down": dense_init(k3, d_ff, d_model, dtype),
    }


def swiglu(params, x):
    g = jax.nn.silu(x @ params["gate"])
    return (g * (x @ params["up"])) @ params["down"]


def softmax_xent(logits: jnp.ndarray, labels: jnp.ndarray, onehot: bool = False) -> jnp.ndarray:
    """Per-position cross entropy, fp32; logits [..., V], labels [...].

    onehot=True (perf iteration #2, ``cfg.opt_onehot_xent``): the picked-logit
    term uses a one-hot contraction instead of a gather — with the vocab dim
    sharded over the model axis, a gather forces an all-gather of the full
    fp32 logits, while iota-compare + multiply + reduce stays local.
    """
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    if onehot:
        oh = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
        picked = jnp.sum(lf * oh, axis=-1)
    else:
        picked = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    return lse - picked
