"""Model FLOPs of training a dense decoder, counted from its shapes.

PaLM's convention (Chowdhery et al. 2022, App. B): a token of training costs
``6 N + 12 L H Q T`` FLOPs, where N counts every parameter, L the layers,
H * Q the heads' total width and T the sequence length.  Recomputation is not
counted.  With tied embeddings N holds the matrix once, which is the output
product's share; an untied model's input table is a lookup and is left out.
"""
from __future__ import annotations

from .spec import ModelShape
from .weights import leaf_shapes


def param_count(s: ModelShape) -> int:
    total = 0
    for shape, _ in leaf_shapes(s).values():
        n = 1
        for d in shape:
            n *= d
        total += n
    return total


def train_flops_per_token(s: ModelShape, seq_len: int) -> float:
    n = param_count(s) - (0 if s.tied else s.vocab * s.d_model)
    return 6.0 * n + 12.0 * s.layers * s.heads * s.head_dim * seq_len
