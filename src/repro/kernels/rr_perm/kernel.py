"""Pallas kernel: on-device stateless RR index generation.

One grid program per cohort slot: given the slot's stream key (seed, client,
round already folded in on the host side — O(C) work), its dataset size and
steps-per-epoch, the kernel materializes the slot's whole [K_max * B] index
stream by running the swap-or-not cipher (see ``ref.py``) element-wise on the
VPU.  No HBM traffic besides the [C, K_max, B] int32 output — the permutation
is *computed*, not stored, so per-round memory stays O(cohort) regardless of
population size.

The per-slot scalars are scalar-prefetched into SMEM and read at the
program's index.  The output is laid out [C, 1, K*B] so each program's
``(1, K*B)`` row block spans the array's last two dimensions — which the TPU
lowering accepts for any K*B (row/column of a step are derived from the
in-block iota, so no tiling constraint on small B).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import fmix32, key_combine, swap_or_not


def _rr_kernel(prekey_ref, n_ref, spe_ref, out_ref, *, B, K, rounds, mode):
    dt = jnp.uint32
    i = pl.program_id(0)
    key0 = prekey_ref[i]
    n = n_ref[i].astype(dt)
    spe = spe_ref[i]
    t = jax.lax.broadcasted_iota(jnp.int32, (1, K * B), 1)
    k = t // B                                         # local step
    e = k // spe                                       # epoch
    flat = (k % spe) * B + t % B                       # position within epoch
    key_e = key_combine(key0, e.astype(dt), jnp)
    if mode == "wr":
        out = fmix32(key_combine(key_e, flat.astype(dt), jnp), jnp) % n
    else:
        out = swap_or_not(flat.astype(dt) % n, n, key_e, rounds, jnp)
    out_ref[...] = out.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("B", "K", "rounds", "mode", "interpret"))
def rr_indices_kernel(prekey, sizes, spe, *, B: int, K: int, rounds: int = 24,
                      mode: str = "rr", interpret: bool = False):
    """[C] per-slot scalars -> [C, K, B] int32 index matrix (device)."""
    (C,) = prekey.shape
    out = pl.pallas_call(
        functools.partial(_rr_kernel, B=B, K=K, rounds=rounds, mode=mode),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(C,),
            in_specs=[],
            out_specs=pl.BlockSpec((pl.Squeezed(), 1, K * B),
                                   lambda i, *_: (i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((C, 1, K * B), jnp.int32),
        interpret=interpret,
    )(prekey, sizes, spe)
    return out.reshape(C, K, B)
