"""Point-wise relative-error quantization (paper §4.3, Alg. 2) — jnp reference.

Scheme (per real plane of a complex SV block):

1. sign bitmap          s_i = (x_i < 0)                       (1 bit/elem)
2. log transform        L_i = log2 |x_i|
3. absolute-bound       quantize L with step 2*b_a, b_a = log2(1 + b_r)
   quantization         => point-wise relative error <= b_r  (Eq. 1/2)

Codes are anchored at the block maximum:  code = CODE_MAX - round((l_max -
L)/step), clipped to [1, CODE_MAX]; code 0 is the exact-zero escape.  With
uint16 codes and b_r = 1e-3 the representable dynamic range below the block
max is ~189 log2 units (~10^57): anything smaller is quantized to exact 0.
(That floor technically breaks the *relative* bound for those elements, but
they are < 2^-189 of the block max — beneath f32 resolution of any inner
product; the paper's fixed-length bitcomp quantizer makes the same trade.)
Additionally, SUBNORMAL magnitudes (|x| < 2^-126) may reconstruct to exact
0 under XLA's flush-to-zero arithmetic — the bound is guaranteed for
normal floats (hypothesis found this edge; tests/test_compression.py).

All arithmetic is float32 so this file doubles as the bit-exact oracle for
the Pallas quantize/dequantize kernels (kernels/ref.py re-exports it).
It is the *host* codec, so it always runs on the CPU backend, also in a
process whose default device is an accelerator.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "PwRelParams", "quantize_plane", "dequantize_plane",
    "CODE_MAX", "log_step",
]

CODE_MAX = 65535  # uint16 code space; 0 = exact zero escape


def log_step(b_r: float) -> float:
    """Quantization step in log2 domain: 2 * b_a = 2 * log2(1 + b_r)."""
    return float(2.0 * np.log2(1.0 + b_r))


@dataclass(frozen=True)
class PwRelParams:
    b_r: float = 1e-3  # the paper's default point-wise relative bound

    @property
    def step(self) -> float:
        return log_step(self.b_r)


@partial(jax.jit, static_argnames=("step",))
def _quantize(x: jax.Array, step: float):
    absx = jnp.abs(x).astype(jnp.float32)
    signs = x < 0
    max_abs = jnp.max(absx)
    l_max = jnp.where(max_abs > 0, jnp.log2(jnp.maximum(max_abs, 1e-45)), 0.0)
    L = jnp.log2(jnp.maximum(absx, 1e-45))          # -149.. for subnormal floor
    d = jnp.round((l_max - L) / jnp.float32(step))
    codes_f = jnp.float32(CODE_MAX) - d
    codes_f = jnp.where(absx <= 0, 0.0, codes_f)
    codes = jnp.clip(codes_f, 0.0, float(CODE_MAX)).astype(jnp.int32)
    return codes, signs, l_max


def _on_host(x, dtype) -> jax.Array:
    return jax.device_put(np.asarray(x, dtype), jax.local_devices(
        backend="cpu")[0])


def quantize_plane(x, params: PwRelParams):
    """f32 plane -> (uint16 codes, bool signs, f32 l_max scalar)."""
    codes, signs, l_max = _quantize(_on_host(x, np.float32), params.step)
    return codes.astype(jnp.uint16), signs, l_max


@partial(jax.jit, static_argnames=("step",))
def _dequantize(codes: jax.Array, signs: jax.Array, l_max: jax.Array,
                step: float) -> jax.Array:
    d = jnp.float32(CODE_MAX) - codes.astype(jnp.float32)
    mag = jnp.exp2(l_max - d * jnp.float32(step))
    mag = jnp.where(codes == 0, 0.0, mag)
    return jnp.where(signs, -mag, mag).astype(jnp.float32)


def dequantize_plane(codes, signs, l_max, params: PwRelParams):
    return _dequantize(_on_host(codes, np.int32), _on_host(signs, bool),
                       _on_host(l_max, np.float32), params.step)
