"""The dense decoder family: RMSNorm, rotary embedding on the halves of each
head, multi-head attention with optional QKV bias, SwiGLU, tied or separate
head; every layer alike, stacked on a leading axis under ``blocks``.

A family module gives what the shared benchmark code needs of one kind of
model (``bench/spec.py`` loads it by the configuration's ``family`` key):

* ``Shape`` — the sizes, ``Shape.from_config(cfg)``;
* ``leaf_shapes(s)`` — ``{path: (shape, kind)}`` of the program's layout;
* ``layers(s)`` — the reference's layer order, ``[(stack path, index, fn)]``
  with ``fn(s, quant, p, h) -> (h, aux)`` in float32;
* ``param_count(s)``, ``train_flops_per_token(s, seq_len)``;
* ``arch_config(s, name)`` — the program's ``ArchConfig`` (the only function
  that imports from ``src/``).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import F32, HIGHEST, mm, rmsnorm


@dataclass(frozen=True)
class Shape:
    """A dense decoder's sizes, as the configuration file states them."""

    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    qkv_bias: bool
    tied: bool
    rope_theta: float
    norm_eps: float
    dtype: str
    init_std: float

    @classmethod
    def from_config(cls, cfg: dict) -> "Shape":
        heads = int(cfg["num_attention_heads"])
        return cls(
            layers=int(cfg["num_hidden_layers"]),
            d_model=int(cfg["hidden_size"]),
            heads=heads,
            kv_heads=int(cfg.get("num_key_value_heads", heads)),
            head_dim=int(cfg.get("head_dim", int(cfg["hidden_size"]) // heads)),
            d_ff=int(cfg["intermediate_size"]),
            vocab=int(cfg["vocab_size"]),
            qkv_bias=bool(cfg["qkv_bias"]),
            tied=bool(cfg["tie_word_embeddings"]),
            rope_theta=float(cfg["rope_theta"]),
            norm_eps=float(cfg["rms_norm_eps"]),
            dtype=str(cfg["torch_dtype"]),
            init_std=float(cfg["assumed"]["init_std"]),
        )


def leaf_shapes(s: Shape) -> dict:
    """``{path: (shape, kind)}`` with kind one of normal / ones."""
    L, D, F, V = s.layers, s.d_model, s.d_ff, s.vocab
    q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim
    leaves = {
        "embed": ((V, D), "normal"),
        "blocks/ln1/scale": ((L, D), "ones"),
        "blocks/attn/wq": ((L, D, q), "normal"),
        "blocks/attn/wk": ((L, D, kv), "normal"),
        "blocks/attn/wv": ((L, D, kv), "normal"),
        "blocks/attn/wo": ((L, q, D), "normal"),
        "blocks/ln2/scale": ((L, D), "ones"),
        "blocks/mlp/gate": ((L, D, F), "normal"),
        "blocks/mlp/up": ((L, D, F), "normal"),
        "blocks/mlp/down": ((L, F, D), "normal"),
        "final_norm/scale": ((D,), "ones"),
    }
    if s.qkv_bias:
        leaves.update({"blocks/attn/bq": ((L, q), "normal"),
                       "blocks/attn/bk": ((L, kv), "normal"),
                       "blocks/attn/bv": ((L, kv), "normal")})
    if not s.tied:
        leaves["lm_head"] = ((D, V), "normal")
    return leaves


def rope(x, theta):
    """x [B, T, H, hd]: rotate (first half, second half) of each head."""
    T, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), F32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), F32)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def block(s: Shape, quant, p: dict, h):
    """One decoder layer in float32; ``p`` holds float32 weights.  A dense
    layer adds nothing to the loss."""
    Bsz, T, _ = h.shape
    hd = s.head_dim
    a = p["attn"]
    x = rmsnorm(p["ln1"]["scale"], h, s.norm_eps)
    q, k, v = mm(quant, x, a["wq"]), mm(quant, x, a["wk"]), mm(quant, x, a["wv"])
    if s.qkv_bias:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = rope(q.reshape(Bsz, T, s.heads, hd), s.rope_theta)
    k = rope(k.reshape(Bsz, T, s.kv_heads, hd), s.rope_theta)
    v = v.reshape(Bsz, T, s.kv_heads, hd)
    rep = s.heads // s.kv_heads
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bthd,bshd->bhts", quant(q), quant(k), precision=HIGHEST) / np.sqrt(hd)
    causal = np.tril(np.ones((T, T), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bhts,bshd->bthd", quant(probs), quant(v), precision=HIGHEST)
    h = h + mm(quant, o.reshape(Bsz, T, s.heads * hd), a["wo"])
    m = p["mlp"]
    x = rmsnorm(p["ln2"]["scale"], h, s.norm_eps)
    return h + mm(quant, jax.nn.silu(mm(quant, x, m["gate"])) * mm(quant, x, m["up"]),
                  m["down"]), jnp.float32(0.0)


def layers(s: Shape) -> list:
    return [("blocks", l, block) for l in range(s.layers)]


def param_count(s: Shape) -> int:
    total = 0
    for shape, _ in leaf_shapes(s).values():
        n = 1
        for d in shape:
            n *= d
        total += n
    return total


def train_flops_per_token(s: Shape, seq_len: int) -> float:
    """PaLM's convention (Chowdhery et al. 2022, App. B): a token of training
    costs ``6 N + 12 L H Q T`` FLOPs, where N counts every parameter, L the
    layers, H * Q the heads' total width and T the sequence length.
    Recomputation is not counted.  With tied embeddings N holds the matrix
    once, which is the output product's share; an untied model's input table
    is a lookup and is left out."""
    n = param_count(s) - (0 if s.tied else s.vocab * s.d_model)
    return 6.0 * n + 12.0 * s.layers * s.heads * s.head_dim * seq_len


def arch_config(s: Shape, name: str):
    from repro.configs.base import ArchConfig

    return ArchConfig(
        name=name, family="dense", n_layers=s.layers, d_model=s.d_model,
        n_heads=s.heads, n_kv_heads=s.kv_heads,
        head_dim=0 if s.heads * s.head_dim == s.d_model else s.head_dim,
        d_ff=s.d_ff, vocab=s.vocab, qkv_bias=s.qkv_bias, rope_theta=s.rope_theta,
        tie_embeddings=s.tied, norm_eps=s.norm_eps, dtype=s.dtype)
