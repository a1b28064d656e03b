"""Pallas kernels: stochastic quantize-pack / unpack-dequantize hot path.

One grid program per block of ``ROWS`` chunks: the program loads its
``[ROWS, chunk]`` fp32 slab, computes each row's max-abs scale, draws the
stochastic-rounding uniforms from the counter-based hash chain
(``rr_perm.ref``), biases the signed levels to ``[0, 2L]`` and bit-packs them
``8 // bits`` to the byte — no HBM traffic besides the packed uint8 wire
bytes and one fp32 scale per chunk.  The unpack kernel inverts it.  Both
mirror ``ref.py`` exactly (the equivalence suite holds the numpy / jnp /
Pallas triple bitwise-identical).

TPU layout: row blocks span the full chunk (and packed-byte) width and a
multiple of 32 rows (uint8's native sublane tile), so any ``chunk`` lowers;
the chunk count is padded up to a whole block and the padding sliced off.
Per-chunk keys and scales ride as ``[ROWS, 1]`` columns.  Packing is a
lane de-interleave the vector unit cannot do directly, so it goes through
the MXU: levels shifted into their bit field are summed per byte by a 0/1
grouping matrix (fields are disjoint, so the sum IS the bitwise OR), and
unpacking broadcasts each byte back to its ``8 // bits`` lanes the same way.
Every operand is an integer below 256 — exact in bf16 with f32
accumulation.  ``interpret=True`` on CPU exercises the same code path in
tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..rr_perm.ref import key_combine
from .ref import packed_width

ROWS = 256        # chunks per grid program (a multiple of uint8's 32-row tile)


def _block_rows(nc: int) -> int:
    return min(ROWS, -(-nc // 32) * 32)


def _pad_rows(x, n: int):
    """Zero-pad a 2-D array's rows up to ``n`` (no-op when already ``n``)."""
    return x if x.shape[0] == n else jnp.pad(x, ((0, n - x.shape[0]), (0, 0)))


def _group_matrix(chunk: int, bits: int):
    """[chunk // per, chunk] bf16: G[k, c] = 1 iff lane c packs into byte k."""
    per = 8 // bits
    k = jax.lax.broadcasted_iota(jnp.int32, (chunk // per, chunk), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (chunk // per, chunk), 1)
    return (c // per == k).astype(jnp.bfloat16)


def _field_shift(shape, bits: int):
    """Bit offset of each lane's field within its byte: bits * (lane % per)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return bits * (lane % (8 // bits))


def _u32_to_f32(h):
    """uint32 -> nearest f32, from two exact 16-bit halves (the TPU kernel
    compiler has no unsigned-to-float cast; one rounding in the add, so the
    result equals the direct conversion bit for bit)."""
    as_f32 = lambda z: jax.lax.bitcast_convert_type(z, jnp.int32).astype(jnp.float32)
    return (as_f32(h >> jnp.uint32(16)) * jnp.float32(65536.0)
            + as_f32(h & jnp.uint32(0xFFFF)))


def _quantize_kernel(v_ref, key_ref, group_ref, packed_ref, scale_ref, *, bits):
    L = jnp.float32(2 ** (bits - 1) - 1)
    v = v_ref[...]                                      # [ROWS, chunk] f32
    a = jnp.abs(v)
    scale = jnp.max(a, axis=1, keepdims=True)           # max is order-exact
    safe = jnp.where(scale > 0, scale, jnp.float32(1.0))
    inv = jnp.where(scale > 0, L / safe, jnp.float32(0.0))
    x = a * inv
    pos = jax.lax.broadcasted_iota(jnp.uint32, v.shape, 1)
    u = _u32_to_f32(key_combine(key_ref[...], pos, jnp)) * jnp.float32(2.0**-32)
    q = jnp.clip(jnp.floor(x + u), jnp.float32(0.0), L)
    lv = jnp.where(v < 0, L - q, L + q).astype(jnp.int32)
    if bits < 8:
        shifted = (lv << _field_shift(v.shape, bits)).astype(jnp.bfloat16)
        lv = jax.lax.dot_general(shifted, group_ref[...], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32).astype(jnp.int32)
    packed_ref[...] = lv.astype(jnp.uint8)
    scale_ref[...] = scale


def _dequantize_kernel(packed_ref, scale_ref, group_ref, out_ref, *, bits):
    L = jnp.float32(2 ** (bits - 1) - 1)
    lv = packed_ref[...].astype(jnp.int32)              # [ROWS, chunk//per]
    if bits < 8:
        byte = jnp.dot(lv.astype(jnp.bfloat16), group_ref[...],
                       preferred_element_type=jnp.float32).astype(jnp.int32)
        lv = (byte >> _field_shift(byte.shape, bits)) & (2**bits - 1)
    # multiply-only form — keeps jit bitwise-equal to ref.py (see there)
    out_ref[...] = (lv.astype(jnp.float32) - L) * scale_ref[...] * (jnp.float32(1.0) / L)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def quantize_pack_kernel(v2, keys, *, bits: int, interpret: bool = False):
    """[nc, chunk] f32 + [nc] uint32 -> (packed [nc, chunk//per] uint8,
    scale [nc] f32), one grid program per ``ROWS`` chunks."""
    nc, chunk = v2.shape
    pb = packed_width(chunk, bits)
    rows = _block_rows(nc)
    n = -(-nc // rows) * rows
    packed, scale = pl.pallas_call(
        functools.partial(_quantize_kernel, bits=bits),
        grid=(n // rows,),
        in_specs=[
            pl.BlockSpec((rows, chunk), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((pb, chunk), lambda i: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((rows, pb), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n, pb), jnp.uint8),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ),
        interpret=interpret,
    )(_pad_rows(v2, n), _pad_rows(keys.reshape(nc, 1), n),
      _group_matrix(chunk, bits))
    return packed[:nc], scale[:nc, 0]


@functools.partial(jax.jit, static_argnames=("chunk", "bits", "interpret"))
def unpack_dequantize_kernel(packed, scale, *, chunk: int, bits: int,
                             interpret: bool = False):
    """(packed [nc, chunk//per] uint8, scale [nc] f32) -> [nc, chunk] f32."""
    nc, pb = packed.shape
    assert pb == packed_width(chunk, bits), (pb, chunk, bits)
    rows = _block_rows(nc)
    n = -(-nc // rows) * rows
    out = pl.pallas_call(
        functools.partial(_dequantize_kernel, bits=bits),
        grid=(n // rows,),
        in_specs=[
            pl.BlockSpec((rows, pb), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((pb, chunk), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((rows, chunk), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, chunk), jnp.float32),
        interpret=interpret,
    )(_pad_rows(packed, n), _pad_rows(scale.reshape(nc, 1), n),
      _group_matrix(chunk, bits))
    return out[:nc]
