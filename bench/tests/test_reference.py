"""The plain reference against textbook matrices and the program's dense
oracle, on the CPU.

Each gate is checked on a state with no symmetry to hide a wrong entry:
its result must equal the textbook matrix, placed with ``np.kron``,
applied in complex128 to the same state.  The gates that the existing
cells use must give the same bits as before the gate set grew.
"""
import ast
import hashlib
import math

import jax.numpy as jnp
import numpy as np
import pytest

import harness
from reference import Reference, gate_matrix, simulate

N = 9
SWAP = np.eye(4)[[0, 2, 1, 3]]


def _rot(axis: np.ndarray, theta: float) -> np.ndarray:
    """exp(-i theta/2 axis) for an axis that squares to the identity."""
    eye = np.eye(len(axis))
    return math.cos(theta / 2) * eye - 1j * math.sin(theta / 2) * axis


X = np.array([[0, 1], [1, 0]], complex)
Y = np.array([[0, -1j], [1j, 0]])
Z = np.diag([1.0 + 0j, -1.0])

#: textbook one-qubit matrices, and the arguments the reference takes
ONE_QUBIT = {
    "i": (np.eye(2), ()),
    "y": (Y, ()),
    "z": (Z, ()),
    "s": (np.diag([1, 1j]), ()),
    "sdg": (np.diag([1, -1j]), ()),
    "t": (np.diag([1, np.exp(1j * math.pi / 4)]), ()),
    "tdg": (np.diag([1, np.exp(-1j * math.pi / 4)]), ()),
    "ry": (_rot(Y, 0.7), (0.7,)),
    "rz": (_rot(Z, 1.1), (1.1,)),
    "p": (np.diag([1, np.exp(0.9j)]), (0.9,)),
    "u3": (np.array([[math.cos(0.35), -np.exp(0.5j) * math.sin(0.35)],
                     [np.exp(1.3j) * math.sin(0.35),
                      np.exp(1.8j) * math.cos(0.35)]]), (0.7, 1.3, 0.5)),
}

#: textbook two-qubit matrices in the basis |first second> (the first
#: qubit the more significant), with the control first
TWO_QUBIT = {
    "cz": (np.diag([1, 1, 1, -1]).astype(complex), (), True),
    "crz": (np.diag([1, 1, np.exp(-0.6j), np.exp(0.6j)]), (1.2,), True),
    "cx": (np.eye(4)[[0, 1, 3, 2]].astype(complex), (), True),
    "rxx": (_rot(np.kron(X, X), 0.8), (0.8,), False),
}


def _start() -> tuple[Reference, np.ndarray]:
    """A state with distinct amplitudes, phases and magnitudes, from the
    gates the reference had before: H, RX and RZZ on every qubit."""
    ref = Reference(N)
    for q in range(N):
        ref.apply("h", (q,))
        ref.apply("rx", (q,), (0.3 + 0.17 * q,))
    for q in range(N - 1):
        ref.apply("rzz", (q, q + 1), (0.5 + 0.11 * q,))
    for q in range(N):
        ref.apply("rx", (q,), (0.9 - 0.07 * q,))
    return ref, ref.block(0, 1 << N)


def _embed(m: np.ndarray, low: int) -> np.ndarray:
    """``m`` on the qubits from ``low`` up (its last factor on ``low``),
    the identity elsewhere: state index bit q is qubit q."""
    k = int(math.log2(len(m)))
    return np.kron(np.kron(np.eye(1 << (N - low - k)), m), np.eye(1 << low))


def _rel(got: np.ndarray, want: np.ndarray) -> float:
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.mark.parametrize("q", [2, 8])
@pytest.mark.parametrize("name", sorted(ONE_QUBIT))
def test_one_qubit_gate_matches_its_textbook_matrix(name, q):
    m, args = ONE_QUBIT[name]
    ref, psi = _start()
    ref.apply(name, (q,), args)
    assert _rel(ref.block(0, 1 << N), _embed(m, q) @ psi) < 1e-6


@pytest.mark.parametrize("first_high", [True, False])
@pytest.mark.parametrize("name", sorted(TWO_QUBIT))
def test_two_qubit_gate_matches_its_textbook_matrix(name, first_high):
    """Qubits 6 and 7 (one on the lane axis, one on the row axis), the
    reference's first qubit (the control) the higher or the lower."""
    m, args, _ = TWO_QUBIT[name]
    lo, hi = 6, 7
    qubits = (hi, lo) if first_high else (lo, hi)
    if not first_high:
        m = SWAP @ m @ SWAP
    ref, psi = _start()
    ref.apply(name, qubits, args)
    assert _rel(ref.block(0, 1 << N), _embed(m, lo) @ psi) < 1e-6


#: the program keeps a controlled gate's qubits as (target, control)
_PROGRAM_TARGET_FIRST = {"cx", "cz", "cp", "crz"}


def test_random_circuit_matches_the_programs_dense_oracle():
    from repro.core.dense_engine import simulate_dense
    from repro.core.library import random_circuit
    n = 10
    circuit = random_circuit(n, 300)
    gates = [(g.name, g.qubits[::-1] if g.name in _PROGRAM_TARGET_FIRST
              else g.qubits, g.params) for g in circuit.gates]
    names = {g[0] for g in gates}
    assert {"cx", "rxx", "crz", "u3"} <= names
    got = simulate(n, gates).block(0, 1 << n)
    want = np.asarray(simulate_dense(circuit), np.complex128)
    assert _rel(got, want) <= 1e-5


#: sha256 of the float32 planes (re then im) of each existing cell's
#: test-size reference state, from the reference as it was before its
#: gate set grew
BEFORE = {
    "qft26.single":
        "f1fdc84e53ea6efcf2928c7fca5185976e7afb04800bc1a238c621b803f0e883",
    "qaoa24.single":
        "3ce192b2a646912b2d9c196d7bfd67f5212b84d0561aa25c48ece8dd95d47eb5",
}


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_existing_cells_reference_is_bitwise_unchanged(name):
    """At n = 12, as ``test_cells.py`` sizes the cells, one binding drawn
    from seed 5."""
    from repro.core.library import maxcut_edges
    cell = harness.load_cell(harness.load_benchmark(), name)
    n = 12
    cell.cfg["n_qubits"] = n
    if "edges" in cell.cfg:
        cell.cfg["edges"] = [list(e) for e in maxcut_edges(n)]
    if "x_qubits" in cell.cfg:
        k = len(cell.cfg["x_qubits"]) * n // 26
        cell.cfg["x_qubits"] = list(range(n - k, n))
    binding = cell.family.draw(cell.cfg, np.random.default_rng(5))
    ref = simulate(n, cell.family.gates(cell.cfg, binding), jnp.float32)
    digest = hashlib.sha256(np.asarray(ref.re).tobytes()
                            + np.asarray(ref.im).tobytes()).hexdigest()
    assert digest == BEFORE[name]


def test_reference_knows_every_gate_the_library_builds():
    from repro.core.circuit import CHANNEL_FACTORIES
    from repro.core.library import (CIRCUIT_BUILDERS, qaoa_template,
                                    random_circuit)
    circuits = [build(10) for build in CIRCUIT_BUILDERS.values()]
    circuits.append(qaoa_template(10, 2).bind(
        {"gamma0": 0.1, "beta0": 0.2, "gamma1": 0.3, "beta1": 0.4}))
    circuits.append(random_circuit(10, 3000, seed=1))
    seen = set()
    for c in circuits:
        for g in c.gates:
            seen.add(g.name)
            if g.name != "swap":
                gate_matrix(g.name, g.params)
    for factory in CHANNEL_FACTORIES.values():
        for _, name in factory(0.1):
            seen.add(name)
            gate_matrix(name, ())
    assert len(seen) >= 21
    with pytest.raises(KeyError):
        gate_matrix("no-such-gate", ())


def test_reference_imports_nothing_of_the_program():
    tree = ast.parse(open(harness.BENCH_DIR / "reference.py").read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported <= {"__future__", "math", "functools", "jax", "numpy"}
