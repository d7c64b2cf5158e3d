"""Pallas TPU kernel: blockwise online-softmax (flash) causal attention.

Why it exists (roofline, EXPERIMENTS.md §Perf): the XLA einsum attention
the models lower by default materializes the full (S, S) score matrix —
at train_4k that is the dominant *memory* term for long-seq cells, and
causal masking wastes half the MXU FLOPs.  This kernel streams K/V tiles
through VMEM with running (max, sum) accumulators, never materializing
scores, and skips fully-masked K tiles (the causal upper triangle), which
halves the attention FLOPs.

Layout: q/k/v (BH, S, hd) — batch*heads flattened into the grid's first
dim.  Grid (BH, S/TQ); each program owns one query tile and loops over
its K tiles with `jax.lax.fori_loop`.  hd padded to a lane multiple by
ops.py.  f32 accumulation throughout.

Validated in interpret mode against ref.flash_attention_ref (tests/
test_kernels_flash.py); GQA is handled by the caller replicating KV heads
(zero-copy broadcast under XLA).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .platform import pallas_call

__all__ = ["flash_attention", "DEFAULT_Q_TILE", "DEFAULT_K_TILE"]

DEFAULT_Q_TILE = 256
DEFAULT_K_TILE = 256
NEG_INF = -2.0 ** 30


def _flash_kernel(scale: float, k_tile: int, causal: bool,
                  q_ref, k_ref, v_ref, o_ref):
    q = q_ref[0] * jnp.float32(scale)            # (TQ, hd)
    TQ, hd = q.shape
    S = k_ref.shape[1]
    iq = pl.program_id(1)
    q_start = iq * TQ

    n_kt = S // k_tile
    # causal: K tiles beyond this query tile's end are fully masked
    if causal:
        last = (q_start + TQ + k_tile - 1) // k_tile
        n_live = jnp.minimum(n_kt, last)
    else:
        n_live = n_kt

    def body(kt, carry):
        acc, m, l = carry
        k = jax.lax.dynamic_slice(k_ref[0], (kt * k_tile, 0), (k_tile, hd))
        v = jax.lax.dynamic_slice(v_ref[0], (kt * k_tile, 0), (k_tile, hd))
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (TQ, TK)
        if causal:
            qi = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kj = kt * k_tile + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(qi >= kj, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=1)
        acc_new = acc * alpha[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    acc0 = jnp.zeros((TQ, hd), jnp.float32)
    m0 = jnp.full((TQ,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((TQ,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, n_live, body, (acc0, m0, l0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    q_tile: int = DEFAULT_Q_TILE,
                    k_tile: int = DEFAULT_K_TILE) -> jax.Array:
    """q/k/v: (BH, S, hd) f32 -> (BH, S, hd) f32 (softmax(qk^T/sqrt)v)."""
    BH, S, hd = q.shape
    tq = min(q_tile, S)
    while S % tq:
        tq //= 2
    tk = min(k_tile, S)
    while S % tk:
        tk //= 2
    scale = hd ** -0.5
    grid = (BH, S // tq)
    fn = pallas_call(
        functools.partial(_flash_kernel, scale, tk, causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tq, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, S, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, S, hd), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, tq, hd), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
    )
    return fn(q, k, v)
