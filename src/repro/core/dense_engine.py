"""Dense (uncompressed) state-vector engines.

* ``simulate_dense`` — the reference engine: full state in one array,
  gate-by-gate application via transpose-to-minor + GEMM.  This is the
  oracle that the compressed BMQSIM engine, the Pallas kernels, and the
  fidelity numbers are all validated against.
* ``simulate_dense_sharded`` — an SV-Sim-like distributed baseline: the
  state is sharded over a device mesh axis; gates on "global" qubits
  induce collectives (what BMQSIM's group independence removes).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .circuit import Circuit, Gate

__all__ = [
    "apply_gate_dense",
    "apply_matrix",
    "initial_state",
    "simulate_dense",
    "simulate_dense_sharded",
]


def initial_state(n: int, dtype=jnp.complex64) -> jax.Array:
    """|0...0> as a flat 2^n vector."""
    state = jnp.zeros((2 ** n,), dtype=dtype)
    return state.at[0].set(1.0)


def apply_matrix(state: jax.Array, mat: jax.Array, qubits: tuple[int, ...],
                 n: int) -> jax.Array:
    """Apply a 2^k x 2^k unitary to ``qubits`` of a flat 2^n state.

    Little-endian: qubit q is bit q of the flat index; ``qubits[j]`` is bit j
    of the matrix row/column index.  Implementation: view the state as an
    n-dim (2,)*n tensor whose axis a holds qubit (n-1-a), transpose the
    target qubits to the minor-most axes (qubits[0] last), GEMM, undo.
    """
    k = len(qubits)
    axes = [n - 1 - q for q in qubits]          # tensor axis of each target
    rest = [a for a in range(n) if a not in axes]
    # new axis order: rest ... then qubits[k-1] ... qubits[0]
    perm = rest + [axes[j] for j in range(k - 1, -1, -1)]
    t = state.reshape((2,) * n).transpose(perm).reshape(-1, 2 ** k)
    t = jnp.matmul(t, mat.astype(t.dtype).T,
                   precision=jax.lax.Precision.HIGHEST)
    inv = np.argsort(np.asarray(perm))  # jit-ok: perm is a static python list
    return t.reshape([2] * n).transpose(list(inv)).reshape(-1)


# -- the oracle at TPU scale ------------------------------------------------
#
# A TPU tiles the two minor axes of an array by (8, 128) and pads
# anything narrower, so apply_matrix's (2,)*n view and its (-1, 2^k)
# GEMM operand cost tens of times the state in padding at large n (and
# minutes of compile).  The tiled path below never creates a narrow minor
# axis: index bits 0..6 are lanes, 7..9 sublanes, and every view keeps
# the (8, 128) tile whole.
_LANE_BITS = 7
_TILE_BITS = 10
#: from here up the oracle runs gate by gate on the tiled path
_TILED_MIN_QUBITS = 16


def _swap_bit_groups(state: jax.Array, g: int, n: int) -> jax.Array:
    """Swap index bits 7..9 with bits g..g+2 (g >= 10): a transpose of
    whole (8, 128) tiles."""
    v = state.reshape(1 << (n - g - 3), 8, 1 << (g - _TILE_BITS), 8,
                      1 << _LANE_BITS)
    return v.transpose(0, 3, 2, 1, 4).reshape(-1)


def _lane_operator(mat: jax.Array, qubits: tuple[int, ...], lane: list[int],
                   h_out: int, h_in: int) -> jax.Array:
    """128 x 128 operator on a row's lanes for the block of ``mat`` whose
    non-lane gate bits are ``h_out`` (rows) and ``h_in`` (columns)."""
    x = np.arange(1 << _LANE_BITS)
    rest = ~sum(1 << qubits[j] for j in lane)
    same = (x[:, None] & rest) == (x[None, :] & rest)

    def index(lanes, h):
        idx = np.zeros_like(lanes)
        hi = [j for j in range(len(qubits)) if j not in lane]
        for i, j in enumerate(hi):
            idx |= ((h >> i) & 1) << j
        for j in lane:
            idx |= ((lanes >> qubits[j]) & 1) << j
        return idx
    r = np.broadcast_to(index(x, h_out)[:, None], same.shape)
    c = np.broadcast_to(index(x, h_in)[None, :], same.shape)
    return jnp.where(same, mat[r, c], 0)


def _apply_tiled(state: jax.Array, mat: jax.Array, qubits: tuple[int, ...],
                 n: int) -> jax.Array:
    """``apply_matrix`` for n >= 16 without narrow minor axes: gate bits
    among the lanes are applied by a 128 x 128 operator on the MXU, gate
    bits above the tile by slicing whole tiles, and gate bits among the
    sublanes are first swapped with a free group of three bits above."""
    if any(_LANE_BITS <= q < _TILE_BITS for q in qubits):
        g = next(g for g in range(_TILE_BITS, n - 2, 3)
                 if not any(g <= q < g + 3 for q in qubits))
        moved = tuple(q - _LANE_BITS + g if _LANE_BITS <= q < _TILE_BITS
                      else q for q in qubits)
        out = _apply_tiled(_swap_bit_groups(state, g, n), mat, moved, n)
        return _swap_bit_groups(out, g, n)
    lane = [j for j, q in enumerate(qubits) if q < _LANE_BITS]
    hi = [j for j in range(len(qubits)) if j not in lane]
    # view: one size-2 axis per gate bit above the tile, whole tiles below
    shape, axis_of, top = [], {}, n
    for j in sorted(hi, key=lambda j: -qubits[j]):
        shape += [1 << (top - qubits[j] - 1), 2]
        axis_of[j] = len(shape) - 1
        top = qubits[j]
    shape += [1 << (top - _TILE_BITS), 8, 1 << _LANE_BITS]
    v = state.reshape(shape)

    def part(h):                       # the tiles whose gate bits read h
        idx = [slice(None)] * len(shape)
        for i, j in enumerate(hi):
            idx[axis_of[j]] = (h >> i) & 1
        return v[tuple(idx)]
    out = 0
    for h_out in range(1 << len(hi)):
        acc = 0
        for h_in in range(1 << len(hi)):
            x = part(h_in)
            if lane:
                op = _lane_operator(mat, qubits, lane, h_out, h_in)
                y = jnp.matmul(x.reshape(-1, 1 << _LANE_BITS), op.T,
                               precision=jax.lax.Precision.HIGHEST)
                acc = acc + y.reshape(x.shape)
            else:
                r = sum(((h_out >> i) & 1) << j for i, j in enumerate(hi))
                c = sum(((h_in >> i) & 1) << j for i, j in enumerate(hi))
                acc = acc + mat[r, c] * x
        # place the rows whose gate bits read h_out (a select: no concat)
        mask = True
        for i, j in enumerate(hi):
            ax = jax.lax.broadcasted_iota(jnp.int32, shape, axis_of[j])
            mask = mask & (ax == ((h_out >> i) & 1))
        out = out + jnp.where(mask, jnp.expand_dims(
            acc, tuple(sorted(axis_of.values()))), 0)
    return out.reshape(-1)


def apply_gate_dense(state: jax.Array, gate: Gate, n: int) -> jax.Array:
    return apply_matrix(state, jnp.asarray(gate.matrix), gate.qubits, n)


def simulate_dense(circuit: Circuit, dtype=jnp.complex64,
                   initial: jax.Array | None = None) -> jax.Array:
    """Reference simulation: returns the final flat 2^n state."""
    n = circuit.n_qubits
    state = (initial_state(n, dtype) if initial is None
             else jnp.array(initial, dtype))   # a copy: the gates donate it

    if n >= _TILED_MIN_QUBITS:
        # gate by gate: a diagonal gate is one compiled function for any
        # qubits, a dense one compiles once per qubit tuple
        for gate in circuit.gates:
            mat = np.asarray(gate.matrix)
            if np.array_equal(mat, np.diag(np.diag(mat))):
                state = _diag_gate(state, jnp.asarray(np.diag(mat), dtype),
                                   jnp.asarray(gate.qubits, jnp.int32))
            else:
                state = _dense_gate(state, jnp.asarray(mat, dtype),
                                    qubits=tuple(gate.qubits), n=n)
        return state

    def run(state, mats):
        for gate, mat in zip(circuit.gates, mats):
            state = apply_matrix(state, mat, gate.qubits, n)
        return state

    mats = tuple(jnp.asarray(g.matrix, dtype=dtype) for g in circuit.gates)
    return jax.jit(run)(state, mats)


@partial(jax.jit, donate_argnums=0)
def _diag_gate(state: jax.Array, diag: jax.Array,
               qubits: jax.Array) -> jax.Array:
    """Multiply by a diagonal gate; ``qubits`` is traced, so every
    k-qubit diagonal gate shares one compilation."""
    i = jax.lax.broadcasted_iota(jnp.int32, state.shape, 0)
    sel = sum(((i >> qubits[j]) & 1) << j for j in range(qubits.shape[0]))
    return state * jnp.take(diag, sel, mode="clip")


@partial(jax.jit, donate_argnums=0, static_argnames=("qubits", "n"))
def _dense_gate(state: jax.Array, mat: jax.Array, *,
                qubits: tuple[int, ...], n: int) -> jax.Array:
    """One dense gate on the tiled path, compiled per qubit tuple."""
    return _apply_tiled(state, mat, qubits, n)


def simulate_dense_sharded(circuit: Circuit, mesh: jax.sharding.Mesh,
                           axis: str = "data",
                           dtype=jnp.complex64) -> jax.Array:
    """SV-Sim-like baseline: state sharded over ``axis`` of ``mesh``.

    The state is laid out so the mesh axis shards the MOST significant
    qubits; a gate touching those qubits makes XLA insert collectives
    (all-to-all / collective-permute) — the communication cost that
    BMQSIM's independent SV groups avoid.  Used by the comparison bench.
    """
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

    n = circuit.n_qubits
    n_dev = mesh.shape[axis]
    assert (2 ** n) % n_dev == 0
    # the gate reshapes leave the sharding to the compiler (GSPMD), which
    # the Explicit axes of a ``jax.make_mesh`` mesh would refuse
    mesh = Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))

    sharding = NamedSharding(mesh, P(axis))
    state = jax.device_put(initial_state(n, dtype), sharding)

    def run(state):
        for gate in circuit.gates:
            state = apply_matrix(state, jnp.asarray(gate.matrix), gate.qubits, n)
        return state

    fn = jax.jit(run, in_shardings=sharding, out_shardings=sharding)
    return fn(state)
