"""Attention: GQA (llama/qwen-style, optional QKV bias), MLA (DeepSeek),
sliding-window, cross-attention, and decode caches (linear + ring).

Layouts: activations [B, T, D]; heads [B, T, H, hd]; caches [B, S, KV, hd].

Prefill/train attention is *query-chunked* (lax.scan over query blocks) above
``CHUNK_THRESHOLD`` so the live score tensor is [B, H, qc, Tk] instead of
[B, H, T, T] — this is what makes prefill_32k lowerable without the Pallas
kernel; the Pallas flash kernel (repro.kernels.flash_attention) is the TPU
fast path and is numerically checked against this implementation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from .layers import apply_rope, dense_init, rmsnorm, rmsnorm_init, yarn_frequencies, yarn_mscale

CHUNK_THRESHOLD = 2048
Q_CHUNK = 1024

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Masks
# ---------------------------------------------------------------------------


def band_mask(q_pos: jnp.ndarray, kv_pos: jnp.ndarray, window: int = 0, causal: bool = True):
    """bool [Tq, Tk]; window=0 => unbounded lookback."""
    diff = q_pos[:, None] - kv_pos[None, :]
    m = (diff >= 0) if causal else jnp.ones(diff.shape, dtype=bool)
    if window:
        m = m & (diff < window)
    return m


# ---------------------------------------------------------------------------
# Core attention (GQA-aware, query-chunked)
# ---------------------------------------------------------------------------


def _attend_block(q, k, v, mask, scale):
    """q [B,Tq,H,hd], k/v [B,Tk,KV,hd], mask broadcastable to [B,KV,g,Tq,Tk]."""
    B, Tq, H, hd = q.shape
    KV = k.shape[2]
    g = H // KV
    qg = q.reshape(B, Tq, KV, g, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32) * scale
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(B, Tq, H, v.shape[-1])  # v head dim may differ (MLA)


def attend(q, k, v, q_pos, kv_pos, *, causal=True, window=0, kv_valid=None,
           banded=False, scale=None):
    """Full attention with optional query chunking.

    q [B,Tq,H,hd]; k,v [B,Tk,KV,hd]; q_pos [Tq]; kv_pos [Tk];
    kv_valid optional bool [B,Tk] (decode cache validity); ``scale`` of the
    scores, 1/sqrt(hd) when None.
    """
    B, Tq, H, hd = q.shape
    if scale is None:
        scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)

    def mask_for(qp):
        m = band_mask(qp, kv_pos, window=window, causal=causal)  # [tq, Tk]
        m = m[None, None, None]                                   # [1,1,1,tq,Tk]
        if kv_valid is not None:
            m = m & kv_valid[:, None, None, None, :]
        return m

    if Tq <= CHUNK_THRESHOLD:
        return _attend_block(q, k, v, mask_for(q_pos), scale)

    pad = (-Tq) % Q_CHUNK
    if pad:  # e.g. the MTP head's S-1 positions; padded queries are discarded
        q = jnp.concatenate([q, jnp.zeros((B, pad, H, hd), q.dtype)], axis=1)
        q_pos = jnp.concatenate([q_pos, jnp.broadcast_to(q_pos[-1:], (pad,))])
    Tq_p = Tq + pad
    nq = Tq_p // Q_CHUNK
    qs = q.reshape(B, nq, Q_CHUNK, H, hd).transpose(1, 0, 2, 3, 4)
    ps = q_pos.reshape(nq, Q_CHUNK)
    Tk = k.shape[1]

    from . import _flags

    # banded path: sliding-window attention only ever looks Q_CHUNK+window
    # back, so slice K/V to the band instead of scoring against all Tk
    # (perf iteration #1: cuts the window-masked score tensor by Tk/band).
    band = Q_CHUNK + (window or 0)
    if banded and window and causal and kv_valid is None and Tk > band:
        idxs = jnp.arange(nq)

        def body_band(_, xs):
            qc, pc, qi = xs
            start = jnp.clip(qi * Q_CHUNK - window + 1, 0, Tk - band)
            kb = jax.lax.dynamic_slice_in_dim(k, start, band, axis=1)
            vb = jax.lax.dynamic_slice_in_dim(v, start, band, axis=1)
            kpb = jax.lax.dynamic_slice_in_dim(kv_pos, start, band, axis=0)
            m = band_mask(pc, kpb, window=window, causal=True)[None, None, None]
            return None, _attend_block(qc, kb, vb, m, scale)

        _, out = jax.lax.scan(body_band, None, (qs, ps, idxs),
                              unroll=nq if _flags.UNROLL_INNER else 1)
    else:
        def body(_, xs):
            qc, pc = xs
            return None, _attend_block(qc, k, v, mask_for(pc), scale)

        _, out = jax.lax.scan(body, None, (qs, ps),
                              unroll=nq if _flags.UNROLL_INNER else 1)
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, Tq_p, H, v.shape[-1])
    return out[:, :Tq] if pad else out


# ---------------------------------------------------------------------------
# GQA attention layer
# ---------------------------------------------------------------------------


def gqa_init(key, cfg: ArchConfig, dtype):
    hd = cfg.hd()
    H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.d_model
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], D, H * hd, dtype),
        "wk": dense_init(ks[1], D, KV * hd, dtype),
        "wv": dense_init(ks[2], D, KV * hd, dtype),
        "wo": dense_init(ks[3], H * hd, D, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((KV * hd,), dtype)
        p["bv"] = jnp.zeros((KV * hd,), dtype)
    return p


def _qkv(params, cfg: ArchConfig, x):
    B, T, D = x.shape
    hd = cfg.hd()
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    return (
        q.reshape(B, T, cfg.n_heads, hd),
        k.reshape(B, T, cfg.n_kv_heads, hd),
        v.reshape(B, T, cfg.n_kv_heads, hd),
    )


def gqa_forward(params, cfg: ArchConfig, x, positions, *, window=0, causal=True,
                kv_override=None):
    """Train/prefill path. Returns (out, (k, v)) so callers can build caches.

    kv_override: (k, v, kv_pos) for cross-attention (encoder memory).
    """
    B, T, _ = x.shape
    q, k, v = _qkv(params, cfg, x)
    if kv_override is None:
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_kind)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_kind)
        kv_pos = positions
    else:
        k, v, kv_pos = kv_override
    out = attend(q, k, v, positions, kv_pos, causal=causal, window=window,
                 banded=cfg.opt_banded_window)
    return out.reshape(B, T, -1) @ params["wo"], (k, v)


def gqa_decode(params, cfg: ArchConfig, x, pos, cache, *, window=0, ring=False,
               cross_kv=None):
    """One-token decode. x [B,1,D]; pos scalar int32 (absolute position).

    cache: {"k": [B,S,KV,hd], "v": ...}; ring=True => slot = pos % S.
    cross_kv: (k, v, valid_len) bypasses the cache (encoder memory).
    """
    B = x.shape[0]
    q, k_new, v_new = _qkv(params, cfg, x)
    if cross_kv is not None:
        k, v = cross_kv
        q = q  # no rope on cross-attention
        S = k.shape[1]
        kv_valid = jnp.ones((B, S), dtype=bool)
        out = attend(q, k, v, jnp.full((1,), pos, jnp.int32), jnp.arange(S),
                     causal=False, kv_valid=kv_valid)
        return out.reshape(B, 1, -1) @ params["wo"], cache
    q = apply_rope(q, jnp.full((1,), pos, jnp.int32), cfg.rope_theta, cfg.rope_kind)
    k_new = apply_rope(k_new, jnp.full((1,), pos, jnp.int32), cfg.rope_theta, cfg.rope_kind)
    S = cache["k"].shape[1]
    slot = (pos % S) if ring else pos
    k = jax.lax.dynamic_update_slice(cache["k"], k_new.astype(cache["k"].dtype), (0, slot, 0, 0))
    v = jax.lax.dynamic_update_slice(cache["v"], v_new.astype(cache["v"].dtype), (0, slot, 0, 0))
    slots = jnp.arange(S)
    if ring:
        valid = jnp.where(pos + 1 >= S, jnp.ones((S,), bool), slots <= pos)
    else:
        valid = slots <= pos
    kv_valid = jnp.broadcast_to(valid[None, :], (B, S))
    # positions are baked into the rotated keys; band windowing is enforced by
    # the ring size itself (ring caches are exactly the window), so use a
    # validity-only mask here.
    out = attend(q, k, v, jnp.full((1,), S + 1, jnp.int32), jnp.zeros((S,), jnp.int32),
                 causal=True, kv_valid=kv_valid)
    return out.reshape(B, 1, -1) @ params["wo"], {"k": k, "v": v}


# ---------------------------------------------------------------------------
# MLA (DeepSeek multi-head latent attention)
# ---------------------------------------------------------------------------


def mla_init(key, cfg: ArchConfig, dtype):
    m = cfg.mla
    D, H = cfg.d_model, cfg.n_heads
    qk = m.qk_nope_dim + m.qk_rope_dim
    ks = jax.random.split(key, 8)
    p = {
        "wdkv": dense_init(ks[0], D, m.kv_lora, dtype),
        "wkr": dense_init(ks[1], D, m.qk_rope_dim, dtype),
        "kv_norm": rmsnorm_init(m.kv_lora, dtype),
        "wuk": dense_init(ks[2], m.kv_lora, H * m.qk_nope_dim, dtype),
        "wuv": dense_init(ks[3], m.kv_lora, H * m.v_head_dim, dtype),
        "wo": dense_init(ks[4], H * m.v_head_dim, D, dtype),
    }
    if m.q_lora:
        p["wdq"] = dense_init(ks[5], D, m.q_lora, dtype)
        p["q_norm"] = rmsnorm_init(m.q_lora, dtype)
        p["wuq"] = dense_init(ks[6], m.q_lora, H * qk, dtype)
    else:
        p["wq"] = dense_init(ks[7], D, H * qk, dtype)
    return p


def _mla_rope(x, positions, cfg):
    """Rotate MLA's rope part [..., T, heads, qk_rope_dim]: plain rope, or
    DeepSeek-V2's YaRN frequencies with cos/sin scaled by
    mscale(factor, mscale) / mscale(factor, mscale_all_dim)."""
    y = cfg.mla.yarn
    if y is None:
        return apply_rope(x, positions, cfg.rope_theta, "full")
    ratio = yarn_mscale(y.factor, y.mscale) / yarn_mscale(y.factor, y.mscale_all_dim)
    if ratio != 1.0:   # the rotation is linear: scaling cos and sin scales x
        x = x * jnp.asarray(ratio, x.dtype)
    inv_freq = yarn_frequencies(cfg.mla.qk_rope_dim, cfg.rope_theta, y)
    return apply_rope(x, positions, cfg.rope_theta, "full", inv_freq=inv_freq)


def mla_softmax_scale(cfg) -> float | None:
    """MLA's score scale: 1/sqrt(qk) (None, ``attend``'s default) or, with
    YaRN, mscale(factor, mscale_all_dim)^2 / sqrt(qk)."""
    m = cfg.mla
    if m.yarn is None:
        return None
    ms = yarn_mscale(m.yarn.factor, m.yarn.mscale_all_dim)
    return ms * ms / (m.qk_nope_dim + m.qk_rope_dim) ** 0.5


def _mla_q(params, cfg, x):
    m = cfg.mla
    B, T, _ = x.shape
    qk = m.qk_nope_dim + m.qk_rope_dim
    if m.q_lora:
        cq = rmsnorm(params["q_norm"], x @ params["wdq"], cfg.norm_eps)
        q = cq @ params["wuq"]
    else:
        q = x @ params["wq"]
    q = q.reshape(B, T, cfg.n_heads, qk)
    return q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim :]


def _mla_ckv(params, cfg, x, positions):
    c_kv = rmsnorm(params["kv_norm"], x @ params["wdkv"], cfg.norm_eps)     # [B,T,kv_lora]
    k_rope = (x @ params["wkr"])[:, :, None, :]                              # [B,T,1,rope]
    k_rope = _mla_rope(k_rope, positions, cfg)[:, :, 0]                     # [B,T,rope]
    return c_kv, k_rope


def mla_forward(params, cfg: ArchConfig, x, positions, *, window=0):
    """Train/prefill: expand c_kv into per-head K/V (the "naive" form).

    Returns (out, (c_kv, k_rope)) — the compressed cache entries.
    """
    m = cfg.mla
    B, T, _ = x.shape
    H = cfg.n_heads
    q_nope, q_rope = _mla_q(params, cfg, x)
    q_rope = _mla_rope(q_rope, positions, cfg)
    c_kv, k_rope = _mla_ckv(params, cfg, x, positions)
    k_nope = (c_kv @ params["wuk"]).reshape(B, T, H, m.qk_nope_dim)
    v = (c_kv @ params["wuv"]).reshape(B, T, H, m.v_head_dim)
    # build full q/k with shared rope part broadcast to all heads
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, :, None, :], q_rope.shape[:3] + (m.qk_rope_dim,))], axis=-1)
    out = attend(q, k, v, positions, positions, causal=True, window=window,
                 banded=cfg.opt_banded_window, scale=mla_softmax_scale(cfg))
    return out.reshape(B, T, -1) @ params["wo"], (c_kv, k_rope)


def mla_decode(params, cfg: ArchConfig, x, pos, cache, *, ring=False):
    """Absorbed decode: scores/values computed in the kv_lora latent space, so
    per-token cost is O(S * kv_lora) and the cache is (kv_lora + rope) wide —
    the whole point of MLA (arXiv:2405.04434 §2.1.2)."""
    m = cfg.mla
    B = x.shape[0]
    H = cfg.n_heads
    q_nope, q_rope = _mla_q(params, cfg, x)                                  # [B,1,H,*]
    q_rope = _mla_rope(q_rope, jnp.full((1,), pos, jnp.int32), cfg)
    c_new, kr_new = _mla_ckv(params, cfg, x, jnp.full((1,), pos, jnp.int32))
    S = cache["c_kv"].shape[1]
    slot = (pos % S) if ring else pos
    c_kv = jax.lax.dynamic_update_slice(cache["c_kv"], c_new.astype(cache["c_kv"].dtype), (0, slot, 0))
    k_rope = jax.lax.dynamic_update_slice(cache["k_rope"], kr_new.astype(cache["k_rope"].dtype), (0, slot, 0))
    slots = jnp.arange(S)
    valid = jnp.where(pos + 1 >= S, jnp.ones((S,), bool), slots <= pos) if ring else (slots <= pos)

    wuk = params["wuk"].reshape(m.kv_lora, H, m.qk_nope_dim)
    q_c = jnp.einsum("bqhn,lhn->bqhl", q_nope, wuk)                          # absorb W_uk
    scores = jnp.einsum("bqhl,bsl->bhqs", q_c, c_kv)
    scores = scores + jnp.einsum("bqhr,bsr->bhqs", q_rope, k_rope)
    scale = mla_softmax_scale(cfg)
    if scale is None:
        scale = 1.0 / jnp.sqrt(m.qk_nope_dim + m.qk_rope_dim).astype(jnp.float32)
    scores = scores.astype(jnp.float32) * scale
    scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(c_kv.dtype)
    ctx = jnp.einsum("bhqs,bsl->bqhl", probs, c_kv)                          # latent context
    wuv = params["wuv"].reshape(m.kv_lora, H, m.v_head_dim)
    out = jnp.einsum("bqhl,lhv->bqhv", ctx, wuv)                             # absorb W_uv
    out = out.reshape(B, 1, -1) @ params["wo"]
    return out, {"c_kv": c_kv, "k_rope": k_rope}
