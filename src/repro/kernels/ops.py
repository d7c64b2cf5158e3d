"""Jit'd public wrappers around the Pallas kernels.

Every kernel runs compiled on a TPU and interpreted on the CPU, chosen
from the platform the call is lowered for (see ``kernels/platform.py``);
no wrapper takes an ``interpret`` flag.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..compression.pwrel import log_step
from . import gate_apply as _ga
from . import pack as _pk
from . import quantize as _qz

__all__ = ["apply_fused_gate", "quantize_block", "dequantize_block",
           "pack_codes", "unpack_codes",
           "pack_sign_bitmap", "unpack_sign_bitmap"]


# --------------------------------------------------------------------------
# fused gate application (engine's use_kernel path)
# --------------------------------------------------------------------------

def apply_fused_gate(amps: jax.Array, mat: jax.Array,
                     vqubits: tuple[int, ...], nv: int,
                     diag: bool) -> jax.Array:
    """Apply a fused unitary to a flat 2^nv complex group array.

    Host side does the qubit-minor transpose (an XLA copy); the Pallas
    kernel does the arithmetic on re/im planes.
    ``mat`` is the (2^k, 2^k) unitary — or its (2^k,) diagonal if ``diag``.
    """
    k = len(vqubits)
    K = 2 ** k
    axes = [nv - 1 - q for q in vqubits]
    rest = [a for a in range(nv) if a not in axes]
    perm = rest + [axes[j] for j in range(k - 1, -1, -1)]
    t = amps.reshape((2,) * nv).transpose(perm).reshape(-1, K)
    ar, ai = jnp.real(t).astype(jnp.float32), jnp.imag(t).astype(jnp.float32)
    if diag:
        dr = jnp.real(mat).astype(jnp.float32)
        di = jnp.imag(mat).astype(jnp.float32)
        cr, ci = _ga.diag_apply(ar, ai, dr, di)
    else:
        b = mat.T  # C = A @ U^T
        br = jnp.real(b).astype(jnp.float32)
        bi = jnp.imag(b).astype(jnp.float32)
        cr, ci = _ga.gemm_planes(ar, ai, br, bi)
    out = (cr + 1j * ci).astype(amps.dtype)
    inv = np.argsort(np.asarray(perm))
    return out.reshape([2] * nv).transpose(list(inv)).reshape(-1)


# --------------------------------------------------------------------------
# pwrel quantize / dequantize (device half of the compressor)
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("step",))
def _quantize_jit(x2d, step):
    max_abs = jnp.max(jnp.abs(x2d))
    l_max = jnp.where(max_abs > 0,
                      jnp.log2(jnp.maximum(max_abs, 1e-45)), 0.0)
    l_max = l_max.reshape(1, 1).astype(jnp.float32)
    codes, packed, flags = _qz.quantize_tiles(x2d, l_max, step)
    return codes, packed, flags, l_max


def quantize_block(x: jax.Array, b_r: float):
    """f32 plane (N,) with N % 128 == 0 -> (codes u16 (N,), packed signs
    (N/128, 4) i32, tile flags, l_max scalar)."""
    x = jnp.asarray(x, jnp.float32)
    n = x.shape[0]
    assert n % 128 == 0, f"plane size {n} not lane-aligned"
    x2d = x.reshape(n // 128, 128)
    codes, packed, flags, l_max = _quantize_jit(x2d, log_step(b_r))
    return (codes.reshape(-1).astype(jnp.uint16), packed, flags,
            l_max.reshape(()))


@partial(jax.jit, static_argnames=("step",))
def _dequantize_jit(codes2d, packed, l_max, step):
    return _qz.dequantize_tiles(codes2d, packed,
                                l_max.reshape(1, 1).astype(jnp.float32),
                                step)


def dequantize_block(codes: jax.Array, packed_signs: jax.Array,
                     l_max, b_r: float) -> jax.Array:
    codes = jnp.asarray(codes).astype(jnp.int32)
    n = codes.shape[0]
    out = _dequantize_jit(codes.reshape(n // 128, 128), packed_signs,
                          jnp.asarray(l_max, jnp.float32), log_step(b_r))
    return out.reshape(-1)


# --------------------------------------------------------------------------
# boundary packing (device wire format of the §4.3 codec)
# --------------------------------------------------------------------------

_pack_codes_jit = jax.jit(_pk.pack_codes_tiles)


def pack_codes(codes: jax.Array):
    """codes (N,) in [0, 65535], N % 128 == 0 -> (N/128, 64) i32 u16-pair
    words; a little-endian host view of the result is the row-major uint16
    code stream."""
    codes = jnp.asarray(codes).astype(jnp.int32)
    n = codes.shape[0]
    assert n % 128 == 0, f"code stream size {n} not lane-aligned"
    return _pack_codes_jit(codes.reshape(n // 128, 128))


_unpack_codes_jit = jax.jit(_pk.unpack_codes_tiles)


def unpack_codes(packed: jax.Array) -> jax.Array:
    """(rows, 64) i32 u16-pair words -> (rows*128,) i32 codes."""
    return _unpack_codes_jit(packed).reshape(-1)


_pack_bitmap_jit = jax.jit(_pk.pack_bitmap_tiles)


def pack_sign_bitmap(bits: jax.Array) -> jax.Array:
    """bits (N,) bool/int, N % 128 == 0 -> (N/128, 4) i32 ballot words
    (LSB = lowest lane), matching the pack fused into ``quantize_block``."""
    bits = jnp.asarray(bits).astype(jnp.int32)
    n = bits.shape[0]
    assert n % 128 == 0, f"bitmap size {n} not lane-aligned"
    return _pack_bitmap_jit(bits.reshape(n // 128, 128))


_unpack_bitmap_jit = jax.jit(_pk.unpack_bitmap_tiles)


def unpack_sign_bitmap(packed: jax.Array) -> jax.Array:
    """(rows, 4) i32 ballot words -> (rows*128,) bool signs."""
    return _unpack_bitmap_jit(packed).reshape(-1) == 1
