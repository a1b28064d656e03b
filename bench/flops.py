"""Parameter and training-FLOP counts of a model, counted by its family from
its shapes (``bench/families/<family>.py``): the dense family by PaLM's
``6 N + 12 L H Q T``; a sparse family counts the FLOPs of the parameters a
token is routed through."""
from __future__ import annotations

from .spec import family_of


def param_count(s) -> int:
    return family_of(s).param_count(s)


def train_flops_per_token(s, seq_len: int) -> float:
    return family_of(s).train_flops_per_token(s, seq_len)
