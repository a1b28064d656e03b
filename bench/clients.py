"""The clients' data, owned by the benchmark: dataset sizes and token rows.

A traffic file's ``clients`` block names the distribution of the clients'
dataset sizes.  The token rows are a counter-based hash of (seed, client,
sample, position), drawn in bulk; the harness feeds them to the program's
pipeline through :class:`TokenRows`, and the plain reference reads the same
rows from the seed.  Nothing here depends on how the program draws its own
synthetic data.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


def client_sizes(clients: dict, n: int) -> np.ndarray:
    """Samples per client, int64 [n].

    ``equal``: ``mean`` each.  ``lognormal``: the lognormal with mean
    ``mean`` and coefficient of variation ``cv``, read at the midpoints of
    ``n`` equal-probability strata (no draw, so no seed moves the largest
    client).  Every size is rounded and at least ``min``."""
    kind, mean = clients["sizes"], float(clients["mean"])
    if kind == "equal":
        s = np.full(n, mean)
    elif kind == "lognormal":
        sigma = math.sqrt(math.log1p(float(clients["cv"]) ** 2))
        mu = math.log(mean) - sigma ** 2 / 2
        z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
        s = np.exp(mu + sigma * z)
    else:
        raise ValueError(f"client sizes {kind!r}: expected 'equal' or 'lognormal'")
    return np.maximum(np.round(s), int(clients.get("min", 1))).astype(np.int64)


_GOLDEN = np.uint64(0x9E3779B97F4A7C15)


def _mix(x: np.ndarray) -> np.ndarray:
    """splitmix64's finalizer on uint64 arrays (arithmetic wraps mod 2**64)."""
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def token_rows(seed: int, client: int, samples: np.ndarray, vocab: int,
               seq_len: int) -> np.ndarray:
    """int32 [*samples.shape, seq_len + 1]: the rows of ``client``'s samples,
    uniform over [client % (vocab // 8), vocab), so each client's tokens
    skew its own way."""
    low = client % max(1, vocab // 8)
    key = _mix(np.asarray([seed % 2 ** 64], np.uint64) + _GOLDEN)
    ids = (np.uint64(client) << np.uint64(32)) | np.asarray(samples, np.int64).astype(np.uint64)
    rows = _mix(key ^ _mix(ids))
    pos = (np.arange(1, seq_len + 2, dtype=np.uint64) * _GOLDEN)
    h = _mix(rows[..., None] + pos)
    return (low + h % np.uint64(vocab - low)).astype(np.int32)


@dataclass(frozen=True)
class TokenRows:
    """The task interface the program's ``FederatedPipeline`` reads
    (``spec`` and ``batch``) over :func:`token_rows`."""

    vocab: int
    seq_len: int
    seed: int

    def spec(self) -> dict:
        return {"tokens": (np.int32, (self.seq_len + 1,))}

    def batch(self, client: int, idx: np.ndarray) -> dict:
        return {"tokens": token_rows(self.seed, client, idx, self.vocab, self.seq_len)}
