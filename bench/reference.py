"""Plain dense state-vector reference: the oracle that decides ``correct``.

It imports nothing of the program under test.  A state of n qubits is
two planes (real and imaginary) of shape (2^(n-7), 128): index bit q of
amplitude ``i = row * 128 + lane`` is qubit q (little-endian, as in
QASM).  Every gate is applied by textbook definition with elementwise
arithmetic only, so no matrix product and no precision setting enters:

* a one-qubit gate U on q reads each amplitude's partner ``i ^ 2^q``
  (a roll along the lane axis for q < 7, along the row axis above) and
  forms ``U[b, 0] x[i with bit 0] + U[b, 1] x[i with bit 1]``;
* a two-qubit diagonal gate multiplies by the entry its two bits select;
* SWAP moves amplitudes whose two bits differ to ``i ^ 2^a ^ 2^b``;
* any other two-qubit gate U on (a, b) reads the three partners
  ``i ^ 2^a``, ``i ^ 2^b``, ``i ^ 2^a ^ 2^b`` and forms
  ``sum_m U[k, k ^ m] x[i with k ^ m]``, k the entry of i's two bits;
* the identity ``i`` leaves the state as it is.

A gate is ``(name, qubits, args)``.  A two-qubit gate's entries are
numbered ``bit_a + 2 * bit_b`` for ``qubits = (a, b)``; the controlled
gates take ``(control, target)``: ``("cx", (c, t))``, ``("cz", (c, t))``,
``("cp", (c, t), (lam,))``, ``("crz", (c, t), (theta,))``.  ``swap``,
``rzz`` and ``rxx`` are symmetric in their two qubits.  The one-qubit
gates are ``i x y z h s sdg t tdg rx ry rz p u3``; ``u3`` takes
``(theta, phi, lam)``.

``dtype`` is the planes' storage type and the type every operation
rounds to: float32 for the reference; ``bench/control.py`` also reads
bfloat16, the nearest precision below the complex64 the configurations
state.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["H", "X", "gate_matrix", "Reference", "simulate"]

_LANE_BITS = 7
_LANES = 1 << _LANE_BITS

H = np.array([[1, 1], [1, -1]], np.complex128) / math.sqrt(2)
X = np.array([[0, 1], [1, 0]], np.complex128)


def _rx(theta: float) -> np.ndarray:
    c, s = math.cos(theta / 2), math.sin(theta / 2)
    return np.array([[c, -1j * s], [-1j * s, c]], np.complex128)


def _cp_diag(lam: float) -> np.ndarray:
    return np.array([1, 1, 1, np.exp(1j * lam)], np.complex128)


def _rzz_diag(theta: float) -> np.ndarray:
    e = np.exp(-0.5j * theta)
    return np.array([e, np.conj(e), np.conj(e), e], np.complex128)


def _ry(theta: float) -> np.ndarray:
    c, s = math.cos(theta / 2), math.sin(theta / 2)
    return np.array([[c, -s], [s, c]], np.complex128)


def _rz(theta: float) -> np.ndarray:
    e = np.exp(-0.5j * theta)
    return np.array([[e, 0], [0, np.conj(e)]], np.complex128)


def _p(lam: float) -> np.ndarray:
    return np.array([[1, 0], [0, np.exp(1j * lam)]], np.complex128)


def _u3(theta: float, phi: float, lam: float) -> np.ndarray:
    c, s = math.cos(theta / 2), math.sin(theta / 2)
    return np.array([[c, -np.exp(1j * lam) * s],
                     [np.exp(1j * phi) * s, np.exp(1j * (phi + lam)) * c]],
                    np.complex128)


def _crz_diag(theta: float) -> np.ndarray:
    """(control, target): Rz(theta) on the target where the control is 1."""
    e = np.exp(-0.5j * theta)
    return np.array([1, e, 1, np.conj(e)], np.complex128)


def _rxx(theta: float) -> np.ndarray:
    """exp(-i theta/2 X X): cos on the diagonal, -i sin between entries
    whose two bits both differ."""
    c, s = math.cos(theta / 2), math.sin(theta / 2)
    u = np.eye(4, dtype=np.complex128) * c
    for k in range(4):
        u[k, k ^ 3] = -1j * s
    return u


#: (control, target): entries 1 (control set, target clear) and 3 swap
_CX = np.eye(4, dtype=np.complex128)[[0, 3, 2, 1]]

_FIXED = {
    "i": np.eye(2, dtype=np.complex128),
    "y": np.array([[0, -1j], [1j, 0]], np.complex128),
    "z": np.diag([1, -1]).astype(np.complex128),
    "s": np.diag([1, 1j]).astype(np.complex128),
    "sdg": np.diag([1, -1j]).astype(np.complex128),
    "t": np.diag([1, np.exp(0.25j * math.pi)]).astype(np.complex128),
    "tdg": np.diag([1, np.exp(-0.25j * math.pi)]).astype(np.complex128),
    "cz": np.array([1, 1, 1, -1], np.complex128),
    "cx": _CX,
}
_ROTATIONS = {"ry": _ry, "rz": _rz, "p": _p, "u3": _u3, "crz": _crz_diag,
              "rxx": _rxx}


def gate_matrix(name: str, args: tuple) -> np.ndarray:
    """The 2x2 matrix of a one-qubit gate, the 4 diagonal entries of a
    two-qubit diagonal gate, or the 4x4 matrix of another two-qubit
    gate (entry ``bit_a + 2 * bit_b``)."""
    if name == "h":
        return H
    if name == "x":
        return X
    if name == "rx":
        return _rx(*args)
    if name == "cp":
        return _cp_diag(*args)
    if name == "rzz":
        return _rzz_diag(*args)
    if name in _FIXED:
        return _FIXED[name]
    if name in _ROTATIONS:
        return _ROTATIONS[name](*args)
    raise KeyError(f"the reference has no gate {name!r}")


def _index_bit(shape, q):
    """Bit q of every amplitude's index, as an int32 array of ``shape``.
    ``q`` may be traced."""
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return jnp.where(q < _LANE_BITS, (lane >> jnp.minimum(q, 31)) & 1,
                     (row >> jnp.maximum(q - _LANE_BITS, 0)) & 1)


def _flip(x, q: int):
    """``x[i ^ 2^q]`` for every i (q static)."""
    if q < _LANE_BITS:
        axis, s = 1, 1 << q
    else:
        axis, s = 0, 1 << (q - _LANE_BITS)
    bit = _index_bit(x.shape, q)
    return jnp.where(bit == 1, jnp.roll(x, s, axis), jnp.roll(x, -s, axis))


def _cmul(ar, ai, br, bi):
    return ar * br - ai * bi, ar * bi + ai * br


@partial(jax.jit, static_argnums=(3,), donate_argnums=(0, 1))
def _one_qubit(re, im, u, q: int):
    """u: (2, 2, 2) [re/im, row, col] in the planes' dtype."""
    pr, pi = _flip(re, q), _flip(im, q)
    bit = _index_bit(re.shape, q) == 1
    # out[b] = U[b, b] * x + U[b, 1-b] * partner
    d_r = jnp.where(bit, u[0, 1, 1], u[0, 0, 0])
    d_i = jnp.where(bit, u[1, 1, 1], u[1, 0, 0])
    o_r = jnp.where(bit, u[0, 1, 0], u[0, 0, 1])
    o_i = jnp.where(bit, u[1, 1, 0], u[1, 0, 1])
    ar, ai = _cmul(d_r, d_i, re, im)
    br, bi = _cmul(o_r, o_i, pr, pi)
    return ar + br, ai + bi


@partial(jax.jit, donate_argnums=(0, 1))
def _diag_two(re, im, d, a, b):
    """d: (2, 4) [re/im, bit_a + 2 bit_b]; a, b traced qubit indices."""
    k = _index_bit(re.shape, a) + 2 * _index_bit(re.shape, b)
    dr = jnp.select([k == 0, k == 1, k == 2], [d[0, 0], d[0, 1], d[0, 2]],
                    d[0, 3])
    di = jnp.select([k == 0, k == 1, k == 2], [d[1, 0], d[1, 1], d[1, 2]],
                    d[1, 3])
    return _cmul(dr, di, re, im)


@partial(jax.jit, static_argnums=(2, 3), donate_argnums=(0, 1))
def _swap(re, im, a: int, b: int):
    same = _index_bit(re.shape, a) == _index_bit(re.shape, b)
    return (jnp.where(same, re, _flip(_flip(re, a), b)),
            jnp.where(same, im, _flip(_flip(im, a), b)))


@partial(jax.jit, static_argnums=(3, 4), donate_argnums=(0, 1))
def _two_qubit(re, im, u, a: int, b: int):
    """u: (2, 4, 4) [re/im, row, col], entries ``bit_a + 2 bit_b``."""
    k = _index_bit(re.shape, a) + 2 * _index_bit(re.shape, b)
    partners = ((re, im), (_flip(re, a), _flip(im, a)),
                (_flip(re, b), _flip(im, b)),
                (_flip(_flip(re, a), b), _flip(_flip(im, a), b)))
    out_r = out_i = 0.0
    for m, (pr, pi) in enumerate(partners):
        # the entry U[k, k ^ m] that weighs partner m of an amplitude
        cr, ci = (jnp.select([k == 0, k == 1, k == 2],
                             [u[p, 0, m], u[p, 1, 1 ^ m], u[p, 2, 2 ^ m]],
                             u[p, 3, 3 ^ m]) for p in (0, 1))
        tr, ti = _cmul(cr, ci, pr, pi)
        out_r, out_i = out_r + tr, out_i + ti
    return out_r, out_i


def _planes_of(m: np.ndarray, dtype) -> jax.Array:
    return jnp.asarray(np.stack([m.real, m.imag]), dtype)


class Reference:
    """A dense state on the default device, in planes of ``dtype``."""

    def __init__(self, n: int, dtype=jnp.float32):
        if n < _LANE_BITS + 1:
            raise ValueError(f"the reference needs n >= 8, got {n}")
        self.n = n
        self.dtype = dtype
        rows = 1 << (n - _LANE_BITS)
        self.re = jnp.zeros((rows, _LANES), dtype).at[0, 0].set(1)
        self.im = jnp.zeros((rows, _LANES), dtype)

    def apply(self, name: str, qubits: tuple, args: tuple = ()) -> None:
        if name == "i":
            return
        if name == "swap":
            a, b = qubits
            self.re, self.im = _swap(self.re, self.im, a, b)
        elif len(qubits) == 1:
            u = _planes_of(gate_matrix(name, args), self.dtype)
            self.re, self.im = _one_qubit(self.re, self.im, u, qubits[0])
        else:
            m = _planes_of(gate_matrix(name, args), self.dtype)
            a, b = qubits
            if m.ndim == 2:
                self.re, self.im = _diag_two(self.re, self.im, m,
                                             jnp.int32(a), jnp.int32(b))
            else:
                self.re, self.im = _two_qubit(self.re, self.im, m, a, b)

    def block(self, lo: int, hi: int) -> np.ndarray:
        """Amplitudes ``lo`` to ``hi`` (whole 128-lane rows) as complex128
        on the host."""
        r0, r1 = lo // _LANES, hi // _LANES
        re = np.asarray(self.re[r0:r1].astype(jnp.float32), np.float64)
        im = np.asarray(self.im[r0:r1].astype(jnp.float32), np.float64)
        return (re + 1j * im).reshape(-1)


def simulate(n: int, gates, dtype=jnp.float32) -> Reference:
    """Run a gate list ``[(name, qubits, args), ...]`` from |0...0>."""
    ref = Reference(n, dtype)
    for name, qubits, args in gates:
        ref.apply(name, tuple(qubits), tuple(args))
    return ref
