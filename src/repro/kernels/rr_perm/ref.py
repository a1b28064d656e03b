"""Stateless RR index generation — swap-or-not cipher oracle (jnp + numpy).

The paper's random reshuffling needs one fresh permutation of [0, n_i) per
(client, round, epoch).  The legacy pipeline draws it with a host PCG
generator, which serializes O(C * K_max * B) host work against the jitted
round.  Here the permutation is a *counter-based cipher*: position ``j`` of
the epoch stream maps to

    idx = SoN_K(j)        (K derived from seed, client, round, epoch)

where ``SoN`` is the Hoang–Morris–Rogaway swap-or-not shuffle — an exact
permutation of [0, n) for ANY n (no cycle-walking): each round ``r`` draws a
key ``K_r in [0, n)``, pairs ``x`` with ``x^ = (K_r - x) mod n``, and swaps
the pair iff a hash bit of the pair's canonical element says so.  Both
partners compute the same canonical element, so every round is a product of
disjoint transpositions — a permutation — and the composition over
``rounds`` (default 24) mixes well.

Everything is uint32 arithmetic with wraparound, implemented once over an
array namespace ``xp`` so numpy (host mirror, ``permutation_np``) and
jax.numpy (in-jit reference, ``rr_indices_ref``) produce bitwise-identical
streams.  The Pallas kernel (``kernel.py``) mirrors the same math.

Round-key modulo bias is ~ n / 2^32 — negligible for client datasets.
"""
from __future__ import annotations

import numpy as np

from ...utils.tags import TAG_RR

_INIT = 0x9E3779B9     # golden-ratio seed of the key chain
_TAG_RR = TAG_RR       # registry: utils/tags.py (reshuffle.py convention)


def fmix32(h, xp):
    """murmur3 finalizer — the 32-bit avalanche at the core of every hash."""
    dt = xp.uint32
    h = h ^ (h >> dt(16))
    h = h * dt(0x85EBCA6B)
    h = h ^ (h >> dt(13))
    h = h * dt(0xC2B2AE35)
    h = h ^ (h >> dt(16))
    return h


def key_combine(h, v, xp):
    """Fold one more value into a running uint32 key (boost::hash_combine)."""
    dt = xp.uint32
    # ≥1-d on purpose: numpy demotes 0-d arrays to scalars, whose ufuncs warn
    # on the wraparound this hash relies on
    v = xp.atleast_1d(xp.asarray(v)).astype(dt)
    return fmix32(h ^ (v + dt(0x9E3779B9) + (h << dt(6)) + (h >> dt(2))), xp)


def stream_key(seed: int, client, rnd, xp):
    """The (seed, client, round) part of the key chain; epoch folds in later.

    ``client`` / ``rnd`` may be arrays (vectorized) or ints; ``seed`` is
    static.  The chain order is fixed — the numpy and jnp paths must agree.
    """
    dt = xp.uint32
    h = fmix32(xp.atleast_1d(xp.asarray((_INIT ^ _TAG_RR) & 0xFFFFFFFF, dt)), xp)
    h = key_combine(h, xp.asarray(seed & 0xFFFFFFFF, dt), xp)
    h = key_combine(h, client, xp)
    h = key_combine(h, rnd, xp)
    return h


def swap_or_not(x, n, key, rounds: int, xp):
    """Apply the cipher to ``x`` (uint32, < n) under per-element ``key``.

    ``n`` and ``key`` broadcast against ``x``; n must be < 2^31 so that
    ``key + n - x`` cannot wrap.  Returns uint32 in [0, n).
    """
    dt = xp.uint32
    for r in range(rounds):
        kr_key = key_combine(key, dt(r), xp)
        kr = fmix32(kr_key, xp) % n                    # round key in [0, n)
        partner = (kr + n - x) % n                     # (K_r - x) mod n
        # same for both partners; a compare-select, not xp.maximum — the TPU
        # kernel compiler has no unsigned vector max
        canon = xp.where(x > partner, x, partner)
        bit = key_combine(kr_key, canon, xp) & dt(1)
        x = xp.where(bit == dt(1), partner, x)
    return x


def permutation_np(seed: int, client: int, rnd: int, epoch: int, n: int,
                   rounds: int = 24) -> np.ndarray:
    """The full epoch permutation as a host array (numpy mirror).

    Drop-in for ``reshuffle.epoch_permutation`` — same (client, round, epoch)
    keying, counter-based stream.  Bitwise-equal to what the device backends
    generate for the same arguments.
    """
    key = key_combine(stream_key(seed, np.uint32(client & 0xFFFFFFFF),
                                 np.uint32(rnd & 0xFFFFFFFF), np),
                      np.uint32(epoch & 0xFFFFFFFF), np)
    x = np.arange(n, dtype=np.uint32)
    return swap_or_not(x, np.uint32(n), key, rounds, np).astype(np.int64)


def _positions(spe, B: int, K: int, xp):
    """Per-slot epoch / flat-position grids ([C, K] and [C, K, B])."""
    k = xp.arange(K, dtype=xp.int32)[None, :]
    e = k // spe[:, None]                              # [C, K]
    within = k % spe[:, None]
    b = xp.arange(B, dtype=xp.int32)[None, None, :]
    flat = within[:, :, None] * xp.int32(B) + b        # [C, K, B]
    return e, flat


def rr_indices(prekey, sizes, spe, B: int, K: int, *, rounds: int = 24,
               mode: str = "rr", xp=np):
    """Index matrices [C, K, B] for a whole cohort, statelessly.

    prekey [C] uint32 — ``stream_key(seed, client, rnd)`` per slot;
    sizes [C] int32 (>= 1); spe [C] int32 steps-per-epoch (>= 1).

    mode "rr": position t of epoch e maps to ``SoN(t mod n)`` — exactly the
    wrapped-tail RR semantics of ``reshuffle.local_step_indices`` (every epoch
    is one full pass; the tail of the last partial batch re-wraps within the
    same epoch's permutation).  mode "wr": i.i.d. with replacement, one hash
    per position (the equalized-step / no-reshuffle stream).
    """
    dt = xp.uint32
    e, flat = _positions(spe, B, K, xp)
    key_ce = key_combine(prekey[:, None], e.astype(xp.uint32), xp)[:, :, None]
    n3 = sizes[:, None, None].astype(dt)
    if mode == "wr":
        return (fmix32(key_combine(key_ce, flat.astype(dt), xp), xp) % n3).astype(xp.int32)
    if mode != "rr":
        raise ValueError(mode)
    j = flat.astype(dt) % n3
    return swap_or_not(j, n3, key_ce, rounds, xp).astype(xp.int32)


def rr_indices_ref(prekey, sizes, spe, B: int, K: int, *, rounds: int = 24,
                   mode: str = "rr"):
    """jnp oracle: the in-jit path the Pallas kernel must match bitwise."""
    import jax.numpy as jnp

    return rr_indices(prekey, sizes, spe, B, K, rounds=rounds, mode=mode, xp=jnp)
