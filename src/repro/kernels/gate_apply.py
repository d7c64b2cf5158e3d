"""Pallas TPU kernel: fused-gate application as an MXU GEMM.

TPU adaptation of SV-Sim's scattered pair updates (DESIGN.md §2): after the
host transposes the group tensor so the fused gate's k virtual qubits are
the minor-most bits, applying the 2^k x 2^k unitary is

    C = A @ B,   A: (R, K) group amplitudes, B = U^T: (K, K), K = 2^k.

With the fusion width f = 7, K = 128 — one MXU tile.  Complex arithmetic
runs as four real GEMMs over re/im planes (the MXU has no complex type):

    Cr = Ar Br - Ai Bi,   Ci = Ar Bi + Ai Br.

Grid: 1-D over row tiles of A; B is broadcast to every program instance
and lives in VMEM for the whole call (K=128 => 2 * 64 KiB planes).
A/C tiles are (TR, K) f32 in VMEM; TR = 256 keeps the working set
(2*(TR*K) in + 2*(TR*K) out + 2*K*K weights) * 4 B ~= 1.2 MiB << 16 MiB VMEM.

There is also a diagonal fast path (``diag_apply``): stage partitions of
phase-heavy circuits (QFT's controlled-phase ladders) fuse into diagonal
unitaries, for which the update is an elementwise complex multiply on the
VPU — no MXU pass at all.

``gemm_planes_mid`` is the transpose-eliding sibling used by the stage
scheduler (core/schedule.py): when a gate's qubit axes form a contiguous
block that is *not* minor-most, the group tensor reshapes to
(outer, K, inner) and the update is the batched left-contraction
C[o] = U @ A[o] — the K axis stays in the sublane dimension and ``inner``
stays in the lanes, so no data movement happens at all.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .platform import pallas_call

__all__ = ["gemm_planes", "gemm_planes_batch", "gemm_planes_mid",
           "diag_apply", "DEFAULT_ROW_TILE"]

DEFAULT_ROW_TILE = 256

#: f32 contractions at full f32 precision: the fidelity a simulation
#: reports must not depend on the MXU's default bf16 passes
_dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32,
                         precision=lax.Precision.HIGHEST)


def _gemm_kernel(ar_ref, ai_ref, br_ref, bi_ref, cr_ref, ci_ref):
    ar = ar_ref[...]
    ai = ai_ref[...]
    br = br_ref[...]
    bi = bi_ref[...]
    cr_ref[...] = _dot(ar, br) - _dot(ai, bi)
    ci_ref[...] = _dot(ar, bi) + _dot(ai, br)


def gemm_planes(ar: jax.Array, ai: jax.Array, br: jax.Array, bi: jax.Array,
                *, row_tile: int = DEFAULT_ROW_TILE) -> tuple[jax.Array, jax.Array]:
    """(R, K) x (K, K) complex GEMM over separate re/im f32 planes."""
    R, K = ar.shape
    assert br.shape == (K, K) and bi.shape == (K, K) and ai.shape == (R, K)
    tr = min(row_tile, R)
    while R % tr:       # R, tr are powers of two in every caller; keep safe
        tr //= 2
    grid = (R // tr,)
    a_spec = pl.BlockSpec((tr, K), lambda i: (i, 0))
    b_spec = pl.BlockSpec((K, K), lambda i: (0, 0))
    out_spec = pl.BlockSpec((tr, K), lambda i: (i, 0))
    out_shape = [jax.ShapeDtypeStruct((R, K), jnp.float32)] * 2
    fn = pallas_call(
        _gemm_kernel,
        grid=grid,
        in_specs=[a_spec, a_spec, b_spec, b_spec],
        out_specs=[out_spec, out_spec],
        out_shape=out_shape,
    )
    return fn(ar, ai, br, bi)


def _gemm_batch_kernel(ar_ref, ai_ref, br_ref, bi_ref, cr_ref, ci_ref):
    ar = ar_ref[0]            # (TR, K) row tile of one lane
    ai = ai_ref[0]
    br = br_ref[0]            # (K, K) = lane's own U^T
    bi = bi_ref[0]
    cr_ref[0] = _dot(ar, br) - _dot(ai, bi)
    ci_ref[0] = _dot(ar, bi) + _dot(ai, br)


def gemm_planes_batch(ar: jax.Array, ai: jax.Array,
                      br: jax.Array, bi: jax.Array,
                      *, row_tile: int = DEFAULT_ROW_TILE) -> tuple[jax.Array, jax.Array]:
    """(L, R, K) x (L, K, K) lane-batched complex GEMM on re/im planes.

    The batched-execution sibling of :func:`gemm_planes`: lane ``l`` of A
    contracts against lane ``l`` of B (each lane of a parameter-sweep /
    noise-trajectory batch carries its own unitary), with the grid 2-D
    over (lane, row tiles) so the whole batch is one kernel dispatch.
    ``br``/``bi`` are the per-lane U^T planes, like :func:`gemm_planes`.
    """
    L, R, K = ar.shape
    assert br.shape == (L, K, K) and bi.shape == (L, K, K) \
        and ai.shape == (L, R, K)
    tr = min(row_tile, R)
    while R % tr:       # R, tr are powers of two in every caller; keep safe
        tr //= 2
    grid = (L, R // tr)
    a_spec = pl.BlockSpec((1, tr, K), lambda lane, i: (lane, i, 0))
    b_spec = pl.BlockSpec((1, K, K), lambda lane, i: (lane, 0, 0))
    out_shape = [jax.ShapeDtypeStruct((L, R, K), jnp.float32)] * 2
    fn = pallas_call(
        _gemm_batch_kernel,
        grid=grid,
        in_specs=[a_spec, a_spec, b_spec, b_spec],
        out_specs=[a_spec, a_spec],
        out_shape=out_shape,
    )
    return fn(ar, ai, br, bi)


def _gemm_mid_kernel(ar_ref, ai_ref, br_ref, bi_ref, cr_ref, ci_ref):
    ar = ar_ref[0]            # (K, TI) slab of one outer batch
    ai = ai_ref[0]
    br = br_ref[...]          # (K, K) = U, broadcast to every program
    bi = bi_ref[...]
    cr_ref[0] = _dot(br, ar) - _dot(bi, ai)
    ci_ref[0] = _dot(br, ai) + _dot(bi, ar)


def gemm_planes_mid(ar: jax.Array, ai: jax.Array,
                    br: jax.Array, bi: jax.Array,
                    *, inner_tile: int = DEFAULT_ROW_TILE) -> tuple[jax.Array, jax.Array]:
    """(O, K, I) complex batched left-GEMM C[o] = U @ A[o] on re/im planes.

    ``br``/``bi`` are U's planes (NOT transposed — the contraction is over
    A's middle axis).  Grid is 2-D over (outer batch, inner tiles); the
    inner axis stays minor so the existing memory layout feeds the MXU
    with no transpose.
    """
    O, K, I = ar.shape
    assert br.shape == (K, K) and bi.shape == (K, K) and ai.shape == (O, K, I)
    ti = min(inner_tile, I)
    while I % ti:       # O, K, I are powers of two in every caller
        ti //= 2
    grid = (O, I // ti)
    a_spec = pl.BlockSpec((1, K, ti), lambda o, t: (o, 0, t))
    b_spec = pl.BlockSpec((K, K), lambda o, t: (0, 0))
    out_shape = [jax.ShapeDtypeStruct((O, K, I), jnp.float32)] * 2
    fn = pallas_call(
        _gemm_mid_kernel,
        grid=grid,
        in_specs=[a_spec, a_spec, b_spec, b_spec],
        out_specs=[a_spec, a_spec],
        out_shape=out_shape,
    )
    return fn(ar, ai, br, bi)


def _diag_kernel(ar_ref, ai_ref, dr_ref, di_ref, cr_ref, ci_ref):
    ar = ar_ref[...]
    ai = ai_ref[...]
    dr = dr_ref[...]          # (1, K) broadcast row
    di = di_ref[...]
    cr_ref[...] = ar * dr - ai * di
    ci_ref[...] = ar * di + ai * dr


def diag_apply(ar: jax.Array, ai: jax.Array, dr: jax.Array, di: jax.Array,
               *, row_tile: int = DEFAULT_ROW_TILE) -> tuple[jax.Array, jax.Array]:
    """Elementwise complex multiply by a diagonal (1, K) — VPU path."""
    R, K = ar.shape
    tr = min(row_tile, R)
    while R % tr:
        tr //= 2
    grid = (R // tr,)
    a_spec = pl.BlockSpec((tr, K), lambda i: (i, 0))
    d_spec = pl.BlockSpec((1, K), lambda i: (0, 0))
    out_spec = pl.BlockSpec((tr, K), lambda i: (i, 0))
    out_shape = [jax.ShapeDtypeStruct((R, K), jnp.float32)] * 2
    fn = pallas_call(
        _diag_kernel,
        grid=grid,
        in_specs=[a_spec, a_spec, d_spec, d_spec],
        out_specs=[out_spec, out_spec],
        out_shape=out_shape,
    )
    return fn(ar, ai, dr.reshape(1, K), di.reshape(1, K))
