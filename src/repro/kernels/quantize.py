"""Pallas TPU kernels: point-wise relative-error quantize / dequantize.

The device-resident half of the paper's §4.3 compressor — the part whose
bandwidth matters (the lossless stage runs on host, as bitcomp's does).

Quantize, per (TR, 128) VMEM tile of a f32 plane (VPU elementwise work):

  1. sign bits  s = x < 0
  2. codes      c = CODE_MAX - round((l_max - log2|x|)/step), 0 = exact zero
  3. sign bitmap packed 32 lanes -> one int32 word (4 words / 128 lanes) —
     the TPU analogue of the paper's warp-ballot pack, run on the MXU
     (``pack.ballot_words``)

``quantize_tiles`` also returns per-tile uniformity flags (all-zero codes
/ all-0 signs / all-1 signs) — the "pre-scan" that lets the host RLE
uniform bitmap chunks without touching them again.  XLA reduces them from
the kernel's outputs: a (1, 3) block per tile has no legal TPU layout.

``l_max`` (the block's max log2|x|) is a scalar prologue computed by XLA
(one fused reduction) and passed in as a (1, 1) operand.

Dequantize is the inverse: codes + unpacked signs + l_max -> f32 plane.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..compression.pwrel import CODE_MAX
from .pack import DEFAULT_BLOCK_ROWS, ballot_words, tile_rows_for
from .platform import pallas_call

__all__ = ["quantize_tiles", "dequantize_tiles", "tile_flags",
           "DEFAULT_TILE_ROWS"]

DEFAULT_TILE_ROWS = 8          # pre-scan flag granularity: (8, 128) = one vreg
_LANES = 128
_WORDS = _LANES // 32          # packed int32 bitmap words per row


def _quantize_kernel(step: float, x_ref, lmax_ref, codes_ref, packed_ref):
    x = x_ref[...]                                   # (TR, 128) f32
    l_max = lmax_ref[0, 0]
    absx = jnp.abs(x)
    signs = x < 0.0

    L = jnp.log2(jnp.maximum(absx, 1e-45))
    d = jnp.round((l_max - L) / jnp.float32(step))
    codes_f = jnp.float32(CODE_MAX) - d
    codes_f = jnp.where(absx <= 0.0, 0.0, codes_f)
    codes = jnp.clip(codes_f, 0.0, float(CODE_MAX)).astype(jnp.int32)
    codes_ref[...] = codes

    # -- ballot-style bitmap pack: 32 lanes -> int32 word -------------------
    packed_ref[...] = ballot_words(signs)


def tile_flags(codes: jax.Array, packed_signs: jax.Array,
               tile_rows: int = DEFAULT_TILE_ROWS) -> jax.Array:
    """Per-tile pre-scan flags (all-zero codes, no negative sign, all
    negative) as an (n_tiles, 3) i32 array, reduced by XLA."""
    rows = codes.shape[0]
    n_tiles = rows // tile_rows_for(rows, tile_rows)
    c = codes.reshape(n_tiles, -1)
    p = packed_signs.reshape(n_tiles, -1)
    return jnp.stack([jnp.all(c == 0, axis=1), jnp.all(p == 0, axis=1),
                      jnp.all(p == -1, axis=1)], axis=1).astype(jnp.int32)


def quantize_tiles(x: jax.Array, l_max: jax.Array, step: float,
                   *, tile_rows: int = DEFAULT_TILE_ROWS):
    """x: (rows, 128) f32; l_max: (1,1) f32 -> (codes i32, packed i32,
    flags per ``tile_rows`` rows)."""
    rows, lanes = x.shape
    assert lanes == _LANES, f"plane must be (rows, {_LANES}), got {x.shape}"
    tr = tile_rows_for(rows, DEFAULT_BLOCK_ROWS)
    codes, packed = pallas_call(
        lambda *refs: _quantize_kernel(step, *refs),
        grid=(rows // tr,),
        in_specs=[
            pl.BlockSpec((tr, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tr, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((tr, _WORDS), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, _LANES), jnp.int32),
            jax.ShapeDtypeStruct((rows, _WORDS), jnp.int32),
        ],
    )(x, l_max)
    return codes, packed, tile_flags(codes, packed, tile_rows)


def _dequantize_kernel(step: float, codes_ref, packed_ref, lmax_ref, x_ref):
    codes = codes_ref[...]                           # (TR, 128) i32
    l_max = lmax_ref[0, 0]
    tr = codes.shape[0]
    packed = packed_ref[...]                         # (TR, 4) i32
    lane = jax.lax.broadcasted_iota(jnp.int32, (tr, _WORDS, 32), 2)
    sbits = (packed[:, :, None] >> lane) & 1
    signs = sbits.reshape(tr, _LANES) == 1

    d = jnp.float32(CODE_MAX) - codes.astype(jnp.float32)
    mag = jnp.exp2(l_max - d * jnp.float32(step))
    mag = jnp.where(codes == 0, 0.0, mag)
    x_ref[...] = jnp.where(signs, -mag, mag).astype(jnp.float32)


def dequantize_tiles(codes: jax.Array, packed_signs: jax.Array,
                     l_max: jax.Array, step: float,
                     *, tile_rows: int = DEFAULT_BLOCK_ROWS) -> jax.Array:
    """codes (rows,128) i32 + packed signs (rows,4) i32 -> (rows,128) f32."""
    rows, lanes = codes.shape
    assert lanes == _LANES
    tr = tile_rows_for(rows, tile_rows)
    grid = (rows // tr,)
    fn = pallas_call(
        lambda *refs: _dequantize_kernel(step, *refs),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tr, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((tr, _WORDS), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tr, _LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
    )
    return fn(codes, packed_signs, l_max)
