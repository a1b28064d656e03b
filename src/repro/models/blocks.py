"""Per-family transformer blocks: forward (train/prefill) and decode variants.

Each family provides:
  * ``<fam>_block_init(key, cfg, dtype)``  -> one layer's params (stacked by caller)
  * ``<fam>_block_forward(params, cfg, h, positions)`` -> (h, aux, cache_entry)
  * ``<fam>_block_decode(params, cfg, h, pos, cache)`` -> (h, new_cache)

``cache_entry`` is what prefill produces per layer; it has the same structure
as the decode cache for that family so prefill->decode hand-off is trivial.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from .attention import gqa_decode, gqa_forward, gqa_init, mla_decode, mla_forward, mla_init
from .layers import rmsnorm, rmsnorm_init, swiglu, swiglu_init
from .mamba2 import mamba2_decode, mamba2_forward, mamba2_init
from .moe import moe_forward, moe_init


# -- dense ------------------------------------------------------------------


def dense_block_init(key, cfg: ArchConfig, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": rmsnorm_init(cfg.d_model, dtype),
        "attn": gqa_init(k1, cfg, dtype),
        "ln2": rmsnorm_init(cfg.d_model, dtype),
        "mlp": swiglu_init(k2, cfg.d_model, cfg.d_ff, dtype),
    }


def dense_block_forward(params, cfg: ArchConfig, h, positions, *, window=0, keep_cache=True):
    a, (k, v) = gqa_forward(params["attn"], cfg, rmsnorm(params["ln1"], h, cfg.norm_eps),
                            positions, window=window)
    h = h + a
    h = h + swiglu(params["mlp"], rmsnorm(params["ln2"], h, cfg.norm_eps))
    cache = {"k": k, "v": v} if keep_cache else None
    return h, jnp.float32(0.0), cache


def dense_block_decode(params, cfg: ArchConfig, h, pos, cache, *, window=0, ring=False):
    a, kv = gqa_decode(params["attn"], cfg, rmsnorm(params["ln1"], h, cfg.norm_eps),
                       pos, cache, window=window, ring=ring)
    h = h + a
    h = h + swiglu(params["mlp"], rmsnorm(params["ln2"], h, cfg.norm_eps))
    return h, kv


# -- moe (MLA attention + MoE FFN; leading layers MLA + dense FFN) ------------


def _mla(params, cfg: ArchConfig, h, positions, window):
    with jax.named_scope("mla"):
        a, (c_kv, k_rope) = mla_forward(params["attn"], cfg,
                                        rmsnorm(params["ln1"], h, cfg.norm_eps),
                                        positions, window=window)
    return h + a, {"c_kv": c_kv, "k_rope": k_rope}


def _mla_decode(params, cfg: ArchConfig, h, pos, cache, ring):
    a, kv = mla_decode(params["attn"], cfg, rmsnorm(params["ln1"], h, cfg.norm_eps),
                       pos, cache, ring=ring)
    return h + a, kv


def moe_block_init(key, cfg: ArchConfig, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": rmsnorm_init(cfg.d_model, dtype),
        "attn": mla_init(k1, cfg, dtype),
        "ln2": rmsnorm_init(cfg.d_model, dtype),
        "moe": moe_init(k2, cfg, dtype),
    }


def moe_block_forward(params, cfg: ArchConfig, h, positions, *, window=0, keep_cache=True):
    """-> (h, {"aux": balance loss, "moe_rows": rows computed}, cache)."""
    h, cache = _mla(params, cfg, h, positions, window)
    with jax.named_scope("moe"):
        y, aux, rows = moe_forward(params["moe"], cfg, rmsnorm(params["ln2"], h, cfg.norm_eps))
    return h + y, {"aux": aux, "moe_rows": rows}, (cache if keep_cache else None)


def moe_block_decode(params, cfg: ArchConfig, h, pos, cache, *, ring=False):
    h, kv = _mla_decode(params, cfg, h, pos, cache, ring)
    y, _, _ = moe_forward(params["moe"], cfg, rmsnorm(params["ln2"], h, cfg.norm_eps))
    return h + y, kv


def first_block_init(key, cfg: ArchConfig, dtype):
    """A leading dense layer of a moe stack: MLA, then a SwiGLU of width d_ff."""
    k1, k2 = jax.random.split(key)
    return {
        "ln1": rmsnorm_init(cfg.d_model, dtype),
        "attn": mla_init(k1, cfg, dtype),
        "ln2": rmsnorm_init(cfg.d_model, dtype),
        "mlp": swiglu_init(k2, cfg.d_model, cfg.d_ff, dtype),
    }


def first_block_forward(params, cfg: ArchConfig, h, positions, *, window=0):
    h, cache = _mla(params, cfg, h, positions, window)
    return h + swiglu(params["mlp"], rmsnorm(params["ln2"], h, cfg.norm_eps)), cache


def first_block_decode(params, cfg: ArchConfig, h, pos, cache, *, ring=False):
    h, kv = _mla_decode(params, cfg, h, pos, cache, ring)
    return h + swiglu(params["mlp"], rmsnorm(params["ln2"], h, cfg.norm_eps)), kv


# -- ssm (Mamba2: mixer only, no separate MLP) --------------------------------


def ssm_block_init(key, cfg: ArchConfig, dtype):
    return {"ln1": rmsnorm_init(cfg.d_model, dtype), "mixer": mamba2_init(key, cfg, dtype)}


def ssm_block_forward(params, cfg: ArchConfig, h, positions, *, keep_cache=True):
    y, mcache = mamba2_forward(params["mixer"], cfg, rmsnorm(params["ln1"], h, cfg.norm_eps))
    h = h + y
    return h, jnp.float32(0.0), (mcache if keep_cache else None)


def ssm_block_decode(params, cfg: ArchConfig, h, pos, cache):
    y, new_cache = mamba2_decode(params["mixer"], cfg, rmsnorm(params["ln1"], h, cfg.norm_eps), cache)
    return h + y, new_cache


# -- hybrid (Hymba: parallel attention + SSM branches) ------------------------


def hybrid_block_init(key, cfg: ArchConfig, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "ln1": rmsnorm_init(cfg.d_model, dtype),
        "attn": gqa_init(k1, cfg, dtype),
        "mixer": mamba2_init(k2, cfg, dtype),
        "ln2": rmsnorm_init(cfg.d_model, dtype),
        "mlp": swiglu_init(k3, cfg.d_model, cfg.d_ff, dtype),
        "branch_scale": jnp.full((2,), 0.5, jnp.float32),
    }


def hybrid_block_forward(params, cfg: ArchConfig, h, positions, *, keep_cache=True):
    x = rmsnorm(params["ln1"], h, cfg.norm_eps)
    a, (k, v) = gqa_forward(params["attn"], cfg, x, positions, window=cfg.sliding_window)
    m, mcache = mamba2_forward(params["mixer"], cfg, x)
    s = params["branch_scale"].astype(h.dtype)
    h = h + s[0] * a + s[1] * m
    h = h + swiglu(params["mlp"], rmsnorm(params["ln2"], h, cfg.norm_eps))
    cache = {"k": k, "v": v, "state": mcache["state"], "conv": mcache["conv"]} if keep_cache else None
    return h, jnp.float32(0.0), cache


def hybrid_block_decode(params, cfg: ArchConfig, h, pos, cache):
    x = rmsnorm(params["ln1"], h, cfg.norm_eps)
    a, kv = gqa_decode(params["attn"], cfg, x, pos, {"k": cache["k"], "v": cache["v"]},
                       window=cfg.sliding_window, ring=True)
    m, ms = mamba2_decode(params["mixer"], cfg, x, {"state": cache["state"], "conv": cache["conv"]})
    s = params["branch_scale"].astype(h.dtype)
    h = h + s[0] * a + s[1] * m
    h = h + swiglu(params["mlp"], rmsnorm(params["ln2"], h, cfg.norm_eps))
    return h, {"k": kv["k"], "v": kv["v"], "state": ms["state"], "conv": ms["conv"]}


# -- encoder/decoder blocks (audio enc-dec) -----------------------------------


def enc_block_init(key, cfg: ArchConfig, dtype):
    return dense_block_init(key, cfg, dtype)


def enc_block_forward(params, cfg: ArchConfig, h, positions):
    a, _ = gqa_forward(params["attn"], cfg, rmsnorm(params["ln1"], h, cfg.norm_eps),
                       positions, causal=False)
    h = h + a
    h = h + swiglu(params["mlp"], rmsnorm(params["ln2"], h, cfg.norm_eps))
    return h


def dec_block_init(key, cfg: ArchConfig, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "ln1": rmsnorm_init(cfg.d_model, dtype),
        "self": gqa_init(k1, cfg, dtype),
        "ln_x": rmsnorm_init(cfg.d_model, dtype),
        "cross": gqa_init(k2, cfg, dtype),
        "ln2": rmsnorm_init(cfg.d_model, dtype),
        "mlp": swiglu_init(k3, cfg.d_model, cfg.d_ff, dtype),
    }


def dec_block_forward(params, cfg: ArchConfig, h, positions, enc_kv, *, keep_cache=True):
    """enc_kv: (k_enc, v_enc, enc_positions) — precomputed per layer."""
    a, (k, v) = gqa_forward(params["self"], cfg, rmsnorm(params["ln1"], h, cfg.norm_eps), positions)
    h = h + a
    c, _ = gqa_forward(params["cross"], cfg, rmsnorm(params["ln_x"], h, cfg.norm_eps),
                       positions, causal=False, kv_override=enc_kv)
    h = h + c
    h = h + swiglu(params["mlp"], rmsnorm(params["ln2"], h, cfg.norm_eps))
    cache = {"k": k, "v": v} if keep_cache else None
    return h, cache


def dec_block_decode(params, cfg: ArchConfig, h, pos, cache, *, ring=False):
    """cache: {"k","v" (self), "xk","xv" (cross, fixed)}."""
    a, kv = gqa_decode(params["self"], cfg, rmsnorm(params["ln1"], h, cfg.norm_eps),
                       pos, {"k": cache["k"], "v": cache["v"]}, ring=ring)
    h = h + a
    c, _ = gqa_decode(params["cross"], cfg, rmsnorm(params["ln_x"], h, cfg.norm_eps),
                      pos, None, cross_kv=(cache["xk"], cache["xv"]))
    h = h + c
    h = h + swiglu(params["mlp"], rmsnorm(params["ln2"], h, cfg.norm_eps))
    return h, {"k": kv["k"], "v": kv["v"], "xk": cache["xk"], "xv": cache["xv"]}


def cross_kv(params, cfg: ArchConfig, enc_out):
    """Precompute encoder-memory K/V for one decoder layer's cross-attention."""
    B, S, _ = enc_out.shape
    hd = cfg.hd()
    k = (enc_out @ params["cross"]["wk"])
    v = (enc_out @ params["cross"]["wv"])
    if cfg.qkv_bias:
        k = k + params["cross"]["bk"]
        v = v + params["cross"]["bv"]
    return k.reshape(B, S, cfg.n_kv_heads, hd), v.reshape(B, S, cfg.n_kv_heads, hd)
