"""Stage gate schedule: transpose-minimizing compilation of a fused plan.

The naive stage compute (PR-1, kept as ``EngineConfig.gate_schedule=False``)
brackets *every* fused unitary with a full-group transpose pair:

    transpose(perm_i) -> GEMM -> transpose(perm_i^-1)      # per gate i

i.e. up to two HBM passes over the 2^(b+m) group array per gate beyond the
arithmetic itself.  This module compiles the stage's gate list into a
minimal permutation plan instead, exploiting three facts:

1. **Layouts compose.** Between gate i and gate i+1 the array only needs
   to move from gate i's layout to gate i+1's layout — one transpose
   (``perm_i^-1 ∘ perm_{i+1}``), not two.  The single inverse permutation
   back to the canonical layout is emitted once, at the end of the stage.
2. **The other axes are free.** A GEMM only requires the gate's k qubit
   axes to be contiguous (they are placed just above the 7 lowest other
   axes, so the minor run stays 128 lanes wide); the remaining axes can
   sit in *any* order.  Keeping them in their current order means
   consecutive gates on identical qubit sets — and many overlapping
   sets — need no transpose at all.
3. **Diagonal unitaries are layout-invariant.** A diagonal gate is an
   elementwise multiply; in any bit-permuted layout each amplitude picks
   its entry from the bits of its own index — never a transpose of the
   group array.

The compiled :class:`StageSchedule` is a pure function of the stage plan
``((vqubits, diag), ...)`` and ``nv`` — cached with ``lru_cache`` the same
way the engine caches its jitted stage functions — and executes on the
planes-resident representation: a ``(2, 2^nv)`` f32 stack of re/im planes
(see ``kernels/gate_apply.py`` for why the MXU wants planes, not
complex64).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache

import jax.numpy as jnp
from jax import lax

__all__ = ["TransposeOp", "GemmOp", "MidGemmOp", "DiagOp", "StageSchedule",
           "compile_schedule", "execute_schedule",
           "execute_schedule_batched", "gate_perm"]

_LANE_BITS = 7                 # a TPU vreg row holds 2^7 = 128 f32 lanes
_LANES = 1 << _LANE_BITS
_TILE = 8 * _LANES             # amplitudes in one (8, 128) f32 vreg tile


@dataclass(frozen=True)
class TransposeOp:
    """Permute the (2,)*nv group tensor axes (one full HBM pass)."""

    perm: tuple[int, ...]


@dataclass(frozen=True)
class GemmOp:
    """Apply dense unitary ``mats[idx]`` (stacked (2, K, K) planes of U)
    to the minor-most K = 2^k amplitudes: C = A @ U^T on re/im planes
    (the transpose folds into the contraction).

    ``bmap`` (when set) is a compile-time index-bit permutation applied to
    U's rows and columns — gates whose qubit axes sit minor-most but in a
    different bit order (a CX stored target-first, say) run without any
    group transpose by permuting the tiny K x K operand instead.
    """

    idx: int
    k: int
    bmap: tuple[int, ...] | None = None


@dataclass(frozen=True)
class MidGemmOp:
    """Apply dense unitary ``mats[idx]`` to a *contiguous* axis block that
    is not minor-most — C[o] = U @ A[o] over (outer, K, inner) planes —
    so gates whose qubit axes already sit together (QFT's recurring
    top-qubit unitaries live at the *major* end) apply with zero
    transposes.  ``bmap`` as in :class:`GemmOp`."""

    idx: int
    k: int
    outer: int
    inner: int
    bmap: tuple[int, ...] | None = None


@dataclass(frozen=True)
class DiagOp:
    """Elementwise multiply by diagonal ``mats[idx]`` ((2, K) planes) in
    the *current* layout — never a transpose.

    ``bits[j]`` is the bit of the flat group index that holds the gate's
    bit j in that layout, so entry ``sum_j bit(i, bits[j]) << j`` of the
    diagonal scales amplitude ``i``: one lane-dense pass over the flat
    planes whatever the layout.  ``minor`` marks the layout where the
    gate bits are the lowest index bits in standard order, where the
    Pallas ``diag_apply`` row kernel applies directly.
    """

    idx: int
    k: int
    bits: tuple[int, ...]

    @property
    def minor(self) -> bool:
        return self.bits == tuple(range(self.k))


@dataclass(frozen=True)
class StageSchedule:
    """Compiled op list for one stage + its transpose accounting.

    ``n_transposes`` counts the full-group transposes the schedule
    executes per group; ``n_transposes_naive`` counts what the per-gate
    path would execute for the same plan (a forward + inverse pair per
    gate whose qubits are not already minor-most).
    """

    nv: int
    ops: tuple
    n_transposes: int
    n_transposes_naive: int


def gate_perm(vqubits: tuple[int, ...], nv: int) -> tuple[int, ...]:
    """The per-gate path's canonical transpose: gate axes minor-most
    (qubit 0's axis last), remaining axes ascending."""
    axes = [nv - 1 - q for q in vqubits]
    rest = [a for a in range(nv) if a not in axes]
    return tuple(rest + [axes[j] for j in range(len(axes) - 1, -1, -1)])


def _contiguous_block(vqubits: tuple[int, ...], nv: int,
                      layout: tuple[int, ...]):
    """``(p, bmap)`` if the gate's axes occupy one contiguous run of the
    layout (any bit order), else None.  ``bmap`` is the compile-time
    K-index bit permutation matching the run's actual order (None =
    already canonical)."""
    k = len(vqubits)
    pos = sorted(layout.index(nv - 1 - q) for q in vqubits)
    if pos != list(range(pos[0], pos[0] + k)):
        return None
    p = pos[0]
    sub = layout[p:p + k]
    wbits = [nv - 1 - sub[k - 1 - j] for j in range(k)]  # qubit on bit j
    if wbits == list(vqubits):
        return p, None
    bmap = tuple(
        sum((((r >> j) & 1) << vqubits.index(wbits[j])) for j in range(k))
        for r in range(1 << k))
    return p, bmap


def _diag_op(idx: int, vqubits: tuple[int, ...], nv: int,
             layout: tuple[int, ...]) -> DiagOp:
    # gate bit j's canonical axis is nv-1-q; layout position p is flat bit
    # nv-1-p
    bits = tuple(nv - 1 - layout.index(nv - 1 - q) for q in vqubits)
    return DiagOp(idx, len(vqubits), bits)


@lru_cache(maxsize=1024)
def compile_schedule(plan: tuple[tuple[tuple[int, ...], bool], ...],
                     nv: int) -> StageSchedule:
    """Compile a stage plan into a transpose-minimizing op sequence.

    Args:
        plan: per fused gate, ``(vqubits, is_diagonal)`` — the same tuple
            the engine caches its stage functions on.
        nv: virtual bits of the group array (b + m).
    """
    ident = tuple(range(nv))
    layout: tuple[int, ...] = ident        # position a holds canonical axis
    ops: list = []
    n_transposes = 0
    n_naive = 0
    for idx, (vqubits, diag) in enumerate(plan):
        if gate_perm(vqubits, nv) != ident:
            n_naive += 2                   # per-gate forward + inverse pair
        if diag:
            ops.append(_diag_op(idx, vqubits, nv, layout))
            continue
        k = len(vqubits)
        tail = [nv - 1 - q for q in reversed(vqubits)]
        # gate axes already contiguous in the current layout (any bit
        # order) -> no group transpose: a bit-order mismatch permutes the
        # tiny K x K operand instead, then minor-most runs as A @ U^T and
        # anywhere else as the batched middle contraction U @ A[o]
        block = _contiguous_block(vqubits, nv, layout)
        if block is not None:
            p, bmap = block
            if p == nv - k:
                ops.append(GemmOp(idx, k, bmap=bmap))
            else:
                ops.append(MidGemmOp(idx, k, outer=1 << p,
                                     inner=1 << (nv - p - k), bmap=bmap))
            continue
        head = [a for a in layout if a not in set(tail)]
        # the gate's axes go just above the layout's 7 lowest other axes,
        # which stay a dense 128-lane minor run: minor-most gate axes
        # would leave a K-wide minor axis that a TPU pads to 128 lanes
        # (and compiles minutes longer).  Groups under 8 such rows keep
        # the gate minor-most.
        low = _LANE_BITS if nv >= k + _LANE_BITS + 3 else 0
        target = tuple(head[:len(head) - low] + tail + head[len(head) - low:])
        ops.append(TransposeOp(tuple(layout.index(a) for a in target)))
        n_transposes += 1
        layout = target
        if low:
            ops.append(MidGemmOp(idx, k, outer=1 << (nv - k - low),
                                 inner=1 << low))
        else:
            ops.append(GemmOp(idx, k))
    if layout != ident:
        ops.append(TransposeOp(tuple(layout.index(a) for a in ident)))
        n_transposes += 1
    return StageSchedule(nv=nv, ops=tuple(ops), n_transposes=n_transposes,
                         n_transposes_naive=n_naive)




def _mm(a, b):
    """f32 matmul at full f32 precision (the TPU default is bf16 passes,
    which would cost the simulation its fidelity)."""
    return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)


def _einsum(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST)


def _collapse(perm: tuple[int, ...]):
    """Merge the runs of axes that stay adjacent under ``perm``.

    Returns (sizes of the merged source axes, ``perm`` over them): a
    transpose of the (2,)*nv group becomes one over a few large axes.
    The TPU compiler takes minutes over a rank-nv array of 2s."""
    runs: list[list[int]] = []              # [first source axis, length]
    for a in perm:
        if runs and runs[-1][0] + runs[-1][1] == a:
            runs[-1][1] += 1
        else:
            runs.append([a, 1])
    order = sorted(range(len(runs)), key=lambda r: runs[r][0])
    sizes = tuple(1 << runs[r][1] for r in order)
    return sizes, tuple(order.index(r) for r in range(len(runs)))


def _transpose(x, perm: tuple[int, ...]):
    """Permute the index bits of flat (L, N) planes as ``perm`` permutes
    the axes of the (2,)*nv group tensor."""
    sizes, small = _collapse(perm)
    lanes = x.shape[0]
    y = x.reshape((lanes,) + sizes)
    return y.transpose((0,) + tuple(a + 1 for a in small)).reshape(lanes, -1)


def _operand(mat, bmap: tuple[int, ...] | None):
    """Stacked U planes (..., 2, K, K) -> (br, bi), bit-permuted when
    needed."""
    br, bi = mat[..., 0, :, :], mat[..., 1, :, :]
    if bmap is not None:
        idx = jnp.asarray(bmap)
        br = br[..., idx, :][..., idx]
        bi = bi[..., idx, :][..., idx]
    return br, bi


def _lane_op(u, inner: int, width: int):
    """(..., width, width) = kron(I, u, I_inner): ``u`` (..., K, K) applied
    to the K axis of a row laid out (width / (K * inner), K, inner)."""
    K = u.shape[-1]
    e_m = jnp.eye(width // (K * inner), dtype=u.dtype)
    e_i = jnp.eye(inner, dtype=u.dtype)
    op = (e_m[:, None, None, :, None, None]
          * u[..., None, :, None, None, :, None]
          * e_i[None, None, :, None, None, :])
    return op.reshape(u.shape[:-2] + (width, width))


def _dense(ar, ai, br, bi, outer: int, K: int, inner: int, *,
           use_kernel: bool, shared: bool):
    """C[o] = U @ A[o] over the (L, outer, K, inner) view of flat (L, N)
    planes; ``br``/``bi`` are U's planes, one (K, K) pair for every row
    when ``shared``, else one per row ((L, K, K))."""
    from ..kernels import gate_apply as ga
    lanes, n = ar.shape
    if K * inner <= _LANES and n >= _TILE:
        # the gate's bits lie inside one 128-lane row: a lane-dense
        # (rows, 128) @ (128, 128) product with the operand
        # kron(I, U, I_inner) — no narrow minor axis, which a TPU pads
        # to 128 lanes (groups under one (8, 128) tile keep W = K)
        width = _LANES
        br, bi = _lane_op(br, inner, width), _lane_op(bi, inner, width)
    else:
        width = K if inner == 1 else 0
    if width:
        tr, ti = br.swapaxes(-1, -2), bi.swapaxes(-1, -2)   # U -> U^T
        a2r = ar.reshape(lanes, -1, width)
        a2i = ai.reshape(lanes, -1, width)
        # the Pallas GEMM keeps both (W, W) operands in VMEM: one MXU
        # tile wide at most; wider operands go to XLA's tiled matmul
        if use_kernel and shared and width <= _LANES:
            cr, ci = ga.gemm_planes(a2r.reshape(-1, width),
                                    a2i.reshape(-1, width), tr, ti)
        elif use_kernel and width <= _LANES:
            cr, ci = ga.gemm_planes_batch(a2r, a2i, tr, ti)
        else:
            cr = _mm(a2r, tr) - _mm(a2i, ti)
            ci = _mm(a2r, ti) + _mm(a2i, tr)
    else:
        a3r = ar.reshape(lanes, outer, K, inner)
        a3i = ai.reshape(lanes, outer, K, inner)
        if use_kernel and shared and inner >= _LANES:
            # wide inner axis: lanes stay dense, MXU-shaped kernel
            cr, ci = ga.gemm_planes_mid(a3r.reshape(-1, K, inner),
                                        a3i.reshape(-1, K, inner), br, bi)
        else:
            spec = "jk,loki->loji" if shared else "ljk,loki->loji"
            cr = _einsum(spec, br, a3r) - _einsum(spec, bi, a3i)
            ci = _einsum(spec, br, a3i) + _einsum(spec, bi, a3r)
    return cr.reshape(lanes, -1), ci.reshape(lanes, -1)


def _diag(ar, ai, dr, di, op: DiagOp, *, use_kernel: bool, shared: bool):
    """Scale flat (L, N) planes by diagonal planes ``dr``/``di`` ((K,)
    when ``shared``, else (L, K)) in the current layout."""
    lanes, n = ar.shape
    K = 1 << op.k
    if use_kernel and shared and op.minor and K >= _LANES:
        # full-lane diagonal: the VPU row kernel is worth the call
        from ..kernels.gate_apply import diag_apply
        cr, ci = diag_apply(ar.reshape(-1, K), ai.reshape(-1, K), dr, di)
        return cr.reshape(lanes, -1), ci.reshape(lanes, -1)
    # each amplitude's diagonal entry from its own index bits: one
    # lane-dense pass, whatever the layout
    i = lax.broadcasted_iota(jnp.int32, (n,), 0)
    sel = sum(((i >> b) & 1) << j for j, b in enumerate(op.bits))
    fr, fi = _lookup(dr, sel), _lookup(di, sel)
    return ar * fr - ai * fi, ar * fi + ai * fr


def _lookup(d, sel):
    """``d[..., sel]`` for a short table ``d``, as a chain of selects: the
    TPU compiler takes about ten times longer over the equivalent
    gather."""
    out = jnp.broadcast_to(d[..., :1], d.shape[:-1] + sel.shape)
    for s in range(1, d.shape[-1]):
        out = jnp.where(sel == s, d[..., s:s + 1], out)
    return out


def execute_schedule_batched(sched: StageSchedule, planes, mats, *,
                             use_kernel: bool, shared: bool = False):
    """Run a compiled schedule over an (L, 2, 2^nv) f32 plane stack.

    Each of the L rows is an SV group's (re, im) planes.  When ``shared``
    one set of operands applies to every row — a wave of groups of one
    state: ``mats[i]`` is ``(2, K, K)`` stacked re/im of U for dense
    gates, ``(2, K)`` for diagonal ones.  Otherwise every operand has a
    leading row axis (``(L, 2, K, K)`` / ``(L, 2, K)``) and row ``l``'s
    unitaries apply to row ``l`` — the lanes of a parameter sweep or
    noise-trajectory batch.  Either way the whole stack traces into ONE
    jitted call per stage.

    The planes stay flat: every op reshapes to the view it needs, with
    the gate's axes merged into as few, as large axes as its position
    allows.  ``use_kernel`` selects the Pallas kernels over plain XLA
    contractions.
    """
    nv = sched.nv
    ar, ai = planes[:, 0], planes[:, 1]
    for op in sched.ops:
        if isinstance(op, TransposeOp):
            ar, ai = _transpose(ar, op.perm), _transpose(ai, op.perm)
        elif isinstance(op, (GemmOp, MidGemmOp)):
            K = 1 << op.k
            inner = op.inner if isinstance(op, MidGemmOp) else 1
            br, bi = _operand(mats[op.idx], op.bmap)
            ar, ai = _dense(ar, ai, br, bi, (1 << nv) // (K * inner), K,
                            inner, use_kernel=use_kernel, shared=shared)
        else:                                   # DiagOp
            m = mats[op.idx]
            ar, ai = _diag(ar, ai, m[..., 0, :], m[..., 1, :], op,
                           use_kernel=use_kernel, shared=shared)
    return _stack_planes(ar, ai)


def _stack_planes(ar, ai):
    """(L, 2, N) from (L, N) re and im.  The barrier keeps the stack out
    of the producers' fusion: fused into it, the TPU compiler's fusion
    emitter aborts on some stage programs (an unaligned
    dynamic-update-slice check)."""
    return jnp.stack(lax.optimization_barrier((ar, ai)), axis=1)


def execute_schedule(sched: StageSchedule, planes, mats, *,
                     use_kernel: bool):
    """Run a compiled schedule over one (2, 2^nv) f32 plane stack.

    ``mats[i]`` is gate i's operand in plane form: ``(2, K, K)`` stacked
    re/im of U for dense gates (each op folds its own transpose into the
    contraction), ``(2, K)`` stacked re/im of the diagonal for diagonal
    gates.  Traced under jit by the engine.
    """
    return execute_schedule_batched(sched, planes[None], mats,
                                    use_kernel=use_kernel, shared=True)[0]
