"""Pallas TPU kernel: decode attention reading a pwrel-COMPRESSED KV cache.

The deployment half of EXPERIMENTS.md §Perf climb 1: at the XLA-graph
level, compressed-KV decode shows the *fit* win (uint8 codes halve the
cache footprint) but dequantizing to a bf16 copy before attention gives
back the bandwidth.  This kernel fuses the paper's §4.3 dequantization
into the attention read itself — codes/signs/scale tiles stream HBM→VMEM
(≈2.11× fewer bytes than bf16 K/V) and are expanded in-register, so the
decode memory roofline drops by the compression ratio.

Layout per (BG)-flattened head:
    q      (BG, rep, hd)          f32   query for this step
    codes  (BG, T, hd) uint8      0 = exact-zero escape (k and v)
    signs  (BG, T, hd/8) uint8    packed sign bitmap
    scale  (BG, T, 1)   f32       per-(token, head) log2 max
    out    (BG, rep, hd) f32

Grid: (BG,); the kernel loops over T tiles with running online-softmax
accumulators (same structure as flash_attention.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .platform import pallas_call

__all__ = ["kv_dequant_decode_attention", "KV_RANGE", "KV_STEP"]

KV_RANGE = 16.0            # log2 units below the per-(token,head) max
KV_STEP = KV_RANGE / 254.0
_CODE_MAX = 255.0
NEG_INF = -2.0 ** 30


def _dequant(codes, signs_packed, scale):
    """codes (T, hd) u8 + signs (T, hd/8) u8 + scale (T, 1) -> f32 (T, hd)."""
    T, hd = codes.shape
    d = _CODE_MAX - codes.astype(jnp.float32)
    mag = jnp.exp2(scale - d * jnp.float32(KV_STEP))
    mag = jnp.where(codes == 0, 0.0, mag)
    bits = (signs_packed[:, :, None] >>
            jax.lax.broadcasted_iota(jnp.uint8, (T, hd // 8, 8), 2)) & 1
    signs = bits.reshape(T, hd) == 1
    return jnp.where(signs, -mag, mag)


def _kernel(k_tile: int, q_ref, ck_ref, sk_ref, lk_ref, cv_ref, sv_ref,
            lv_ref, pos_ref, o_ref):
    q = q_ref[0]                                   # (rep, hd) f32
    rep, hd = q.shape
    T = ck_ref.shape[1]
    pos = pos_ref[0, 0]
    scale = hd ** -0.5

    n_tiles = T // k_tile

    def body(t, carry):
        acc, m, l = carry
        sl = lambda ref, w: jax.lax.dynamic_slice(   # noqa: E731
            ref[0], (t * k_tile, 0), (k_tile, w))
        k = _dequant(sl(ck_ref, hd), sl(sk_ref, hd // 8), sl(lk_ref, 1))
        v = _dequant(sl(cv_ref, hd), sl(sv_ref, hd // 8), sl(lv_ref, 1))
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        j = t * k_tile + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(j <= pos, s, NEG_INF)        # causal length mask
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=1)
        acc_new = acc * alpha[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    acc0 = jnp.zeros((rep, hd), jnp.float32)
    m0 = jnp.full((rep,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((rep,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, n_tiles, body, (acc0, m0, l0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def kv_dequant_decode_attention(q, codes_k, signs_k, scale_k,
                                codes_v, signs_v, scale_v, pos, *,
                                k_tile: int = 512):
    """q (BG, rep, hd) f32; cache leaves (BG, T, ...) -> out (BG, rep, hd).

    ``pos``: scalar int32 — last valid cache index (causal mask j <= pos).
    """
    BG, rep, hd = q.shape
    T = codes_k.shape[1]
    tk = min(k_tile, T)
    while T % tk:
        tk //= 2
    grid = (BG,)
    full = lambda w, dt: pl.BlockSpec((1, T, w), lambda b: (b, 0, 0))  # noqa: E731
    fn = pallas_call(
        functools.partial(_kernel, tk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, rep, hd), lambda b: (b, 0, 0)),
            full(hd, jnp.uint8), full(hd // 8, jnp.uint8), full(1, jnp.float32),
            full(hd, jnp.uint8), full(hd // 8, jnp.uint8), full(1, jnp.float32),
            pl.BlockSpec((1, 1), lambda b: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, rep, hd), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BG, rep, hd), jnp.float32),
    )
    pos2d = jnp.asarray(pos, jnp.int32).reshape(1, 1)
    return fn(q, codes_k, signs_k, scale_k, codes_v, signs_v, scale_v, pos2d)
