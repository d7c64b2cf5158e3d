"""Pallas kernels: uint16 code packing and sign-bitmap pack/unpack.

These are the boundary-compression kernels of the device-resident codec
(paper §4.3): after ``quantize.py`` produces int32 codes and ballot-packed
sign words, these kernels shrink what actually crosses the host↔device
boundary —

* ``pack_codes_tiles``    — two uint16 codes per int32 word (lane pairs), so
  a little-endian host view of the words is exactly the row-major uint16
  code stream the lossless stage zlib-encodes.  2 bytes/element on the wire
  instead of 4.
* ``unpack_codes_tiles``  — the inverse, run before ``dequantize_tiles``.
* ``pack_bitmap_tiles`` / ``unpack_bitmap_tiles`` — standalone ballot-style
  sign packing (32 lanes -> one int32 word, LSB = lowest lane), the TPU
  analogue of the paper's warp-ballot bitmap build.  1 bit/element on the
  wire.  ``quantize_tiles`` fuses the same pack (:func:`ballot_words`)
  into its kernel; the standalone version exists for decode-side
  symmetry and for reuse outside the quantizer.

Both packs move data *across* lanes (128 lanes -> 64 or 4 words), and
Mosaic refuses the lane-splitting reshapes (``(tr, 128) -> (tr, 64, 2)``)
and lane-strided reads that would express that directly.  So the packs
run on the MXU instead: a product with a constant 0/power-of-two matrix
gathers and weights lanes into output columns.  Operands are bf16
values that bf16 holds exactly (bits, bytes, powers of two) and the
products accumulate in f32 below 2^24, so every word is exact.  The
unpacks broadcast words back out to lanes, which Mosaic lowers as is.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .platform import pallas_call

__all__ = [
    "pack_codes_tiles", "unpack_codes_tiles",
    "pack_bitmap_tiles", "unpack_bitmap_tiles",
    "ballot_words", "tile_rows_for",
    "CODE_WORDS", "BITMAP_WORDS", "DEFAULT_BLOCK_ROWS",
]

_LANES = 128
CODE_WORDS = _LANES // 2       # int32 words per row of packed uint16 codes
BITMAP_WORDS = _LANES // 32    # int32 words per row of packed sign bits
#: rows of a (rows, 128) plane per grid step: 128 KiB of f32, so a 2^20
#: element plane is 32 steps, not thousands
DEFAULT_BLOCK_ROWS = 256


def tile_rows_for(rows: int, tile_rows: int) -> int:
    """Largest power-of-two divisor of ``rows`` up to ``tile_rows``."""
    tr = min(tile_rows, rows)
    while rows % tr:
        tr //= 2
    return tr


def _lane_matrix(weight):
    """(128, 128) bf16 matrix ``weight(lane, col)`` built from iotas."""
    shape = (_LANES, _LANES)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return weight(lane, col).astype(jnp.float32).astype(jnp.bfloat16)


def _exact_dot(a, w):
    """Integer-valued (tr, 128) @ (128, 128) on the MXU, exact while every
    operand fits bf16 and every output stays below 2^24."""
    a = a.astype(jnp.float32).astype(jnp.bfloat16)
    return jnp.dot(a, w, preferred_element_type=jnp.float32).astype(jnp.int32)


def ballot_words(bits):
    """(tr, 128) int {0, 1} -> (tr, 4) int32 words, bit j of word w =
    lane 32w + j.  Each word is built as two exact 16-bit halves."""
    def half(h):
        return lambda lane, col: jnp.where(
            ((lane >> 5) == col) & (((lane >> 4) & 1) == h),
            1 << (lane & 15), 0)
    lo = _exact_dot(bits, _lane_matrix(half(0)))
    hi = _exact_dot(bits, _lane_matrix(half(1)))
    return (lo | (hi << 16))[:, :BITMAP_WORDS]


def _pack_codes_kernel(codes_ref, out_ref):
    c = codes_ref[...]                       # (TR, 128) i32, values in u16 range
    lo8, hi8 = c & 0xFF, c >> 8

    def pick(parity):                        # column j <- lane 2j + parity
        sel = _lane_matrix(lambda lane, col: lane == 2 * col + parity)
        return _exact_dot(lo8, sel) | (_exact_dot(hi8, sel) << 8)
    out_ref[...] = (pick(0) | (pick(1) << 16))[:, :CODE_WORDS]


def pack_codes_tiles(codes: jax.Array, *,
                     tile_rows: int = DEFAULT_BLOCK_ROWS) -> jax.Array:
    """codes (rows, 128) i32 in [0, 65535] -> (rows, 64) i32 u16-pair words."""
    rows, lanes = codes.shape
    assert lanes == _LANES, f"codes must be (rows, {_LANES}), got {codes.shape}"
    tr = tile_rows_for(rows, tile_rows)
    return pallas_call(
        _pack_codes_kernel,
        grid=(rows // tr,),
        in_specs=[pl.BlockSpec((tr, _LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tr, CODE_WORDS), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, CODE_WORDS), jnp.int32),
    )(codes)


def _unpack_codes_kernel(packed_ref, codes_ref):
    w = packed_ref[...]                      # (TR, 64) i32
    tr = w.shape[0]
    lo = w & 0xFFFF
    hi = (w >> 16) & 0xFFFF
    codes_ref[...] = jnp.stack([lo, hi], axis=-1).reshape(tr, _LANES)


def unpack_codes_tiles(packed: jax.Array, *,
                       tile_rows: int = DEFAULT_BLOCK_ROWS) -> jax.Array:
    """(rows, 64) i32 u16-pair words -> (rows, 128) i32 codes."""
    rows, words = packed.shape
    assert words == CODE_WORDS
    tr = tile_rows_for(rows, tile_rows)
    return pallas_call(
        _unpack_codes_kernel,
        grid=(rows // tr,),
        in_specs=[pl.BlockSpec((tr, CODE_WORDS), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tr, _LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.int32),
    )(packed)


def _pack_bitmap_kernel(bits_ref, out_ref):
    out_ref[...] = ballot_words(bits_ref[...])


def pack_bitmap_tiles(bits: jax.Array, *,
                      tile_rows: int = DEFAULT_BLOCK_ROWS) -> jax.Array:
    """bits (rows, 128) i32/bool -> (rows, 4) i32 ballot words (LSB first)."""
    rows, lanes = bits.shape
    assert lanes == _LANES
    tr = tile_rows_for(rows, tile_rows)
    return pallas_call(
        _pack_bitmap_kernel,
        grid=(rows // tr,),
        in_specs=[pl.BlockSpec((tr, _LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tr, BITMAP_WORDS), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, BITMAP_WORDS), jnp.int32),
    )(bits.astype(jnp.int32))


def _unpack_bitmap_kernel(packed_ref, bits_ref):
    w = packed_ref[...]                      # (TR, 4) i32
    tr = w.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (tr, BITMAP_WORDS, 32), 2)
    bits_ref[...] = ((w[:, :, None] >> lane) & 1).reshape(tr, _LANES)


def unpack_bitmap_tiles(packed: jax.Array, *,
                        tile_rows: int = DEFAULT_BLOCK_ROWS) -> jax.Array:
    """(rows, 4) i32 ballot words -> (rows, 128) i32 bits in {0, 1}."""
    rows, words = packed.shape
    assert words == BITMAP_WORDS
    tr = tile_rows_for(rows, tile_rows)
    return pallas_call(
        _unpack_bitmap_kernel,
        grid=(rows // tr,),
        in_specs=[pl.BlockSpec((tr, BITMAP_WORDS), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tr, _LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.int32),
    )(packed)
