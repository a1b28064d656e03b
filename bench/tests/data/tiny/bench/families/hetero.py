"""A test-only family: a stack of two kinds of layer, each with an auxiliary
loss, to show that the shared benchmark code takes a family it has never
seen as new files alone.

A residual SwiGLU layer of width ``d_ff_first`` under ``first`` (one layer),
then ``layers`` of width ``d_ff`` under ``blocks``; each layer adds
``aux_coef * mean(h_out^2)`` to the loss.  Untied head.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from bench.reference import mm, rmsnorm


@dataclass(frozen=True)
class Shape:
    layers: int
    d_model: int
    d_ff_first: int
    d_ff: int
    vocab: int
    tied: bool
    norm_eps: float
    dtype: str
    init_std: float
    aux_coef: float

    @classmethod
    def from_config(cls, cfg: dict) -> "Shape":
        return cls(layers=int(cfg["num_hidden_layers"]), d_model=int(cfg["hidden_size"]),
                   d_ff_first=int(cfg["first_intermediate_size"]),
                   d_ff=int(cfg["intermediate_size"]), vocab=int(cfg["vocab_size"]),
                   tied=bool(cfg["tie_word_embeddings"]), norm_eps=float(cfg["rms_norm_eps"]),
                   dtype=str(cfg["torch_dtype"]), init_std=float(cfg["assumed"]["init_std"]),
                   aux_coef=float(cfg["aux_coef"]))


def _mlp_leaves(path: str, n: int, d: int, f: int) -> dict:
    return {f"{path}/ln/scale": ((n, d), "ones"), f"{path}/mlp/gate": ((n, d, f), "normal"),
            f"{path}/mlp/up": ((n, d, f), "normal"), f"{path}/mlp/down": ((n, f, d), "normal")}


def leaf_shapes(s: Shape) -> dict:
    leaves = {"embed": ((s.vocab, s.d_model), "normal"),
              "final_norm/scale": ((s.d_model,), "ones"),
              **_mlp_leaves("first", 1, s.d_model, s.d_ff_first),
              **_mlp_leaves("blocks", s.layers, s.d_model, s.d_ff)}
    if not s.tied:
        leaves["lm_head"] = ((s.d_model, s.vocab), "normal")
    return leaves


def mlp_layer(s: Shape, quant, p: dict, h):
    x = rmsnorm(p["ln"]["scale"], h, s.norm_eps)
    m = p["mlp"]
    h = h + mm(quant, jax.nn.silu(mm(quant, x, m["gate"])) * mm(quant, x, m["up"]), m["down"])
    return h, s.aux_coef * jnp.mean(h * h)


def layers(s: Shape) -> list:
    return [("first", 0, mlp_layer)] + [("blocks", l, mlp_layer) for l in range(s.layers)]


def param_count(s: Shape) -> int:
    total = 0
    for shape, _ in leaf_shapes(s).values():
        n = 1
        for d in shape:
            n *= d
        total += n
    return total


def train_flops_per_token(s: Shape, seq_len: int) -> float:
    return 6.0 * (param_count(s) - (0 if s.tied else s.vocab * s.d_model))


def arch_config(s: Shape, name: str):
    from repro.configs.base import ArchConfig

    return ArchConfig(name=name, family="dense", n_layers=1 + s.layers, d_model=s.d_model,
                      d_ff=s.d_ff, vocab=s.vocab, tie_embeddings=s.tied,
                      norm_eps=s.norm_eps, dtype=s.dtype)
