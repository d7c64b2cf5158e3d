"""Quantum Fourier transform (QASMBench ``qft`` family) of a basis state.

The input |x> is prepared by X gates on the configuration's
``x_qubits``.  On |0...0> every controlled phase multiplies amplitudes
that are zero and the final state is uniform, which every precision
computes exactly; with the top qubits set the controlled phases among
them act, and the output's phases repeat with a short period, so the
state stays compressible.  Nothing is drawn from the seed: every seed
runs the same work.
"""
from __future__ import annotations

import math


def program_circuit(cfg: dict):
    """The circuit as the system under test builds it."""
    from repro.core import Circuit, build_circuit
    n = cfg["n_qubits"]
    prep = Circuit(n)
    for q in cfg["x_qubits"]:
        prep.x(q)
    qft = build_circuit("qft", n, **cfg["circuit_args"])
    return Circuit(n, list(prep.gates) + list(qft.gates))


def draw(cfg: dict, rng) -> dict | None:
    return None


def gates(cfg: dict, params: dict | None) -> list:
    """X on each of ``x_qubits``, then the textbook QFT as ``(name,
    qubits, args)``: H on each qubit from the top, each followed by
    controlled phases pi / 2^(q-j) from every lower qubit j, then the
    swaps that reverse the qubit order."""
    n = cfg["n_qubits"]
    out = [("x", (q,), ()) for q in cfg["x_qubits"]]
    for q in range(n - 1, -1, -1):
        out.append(("h", (q,), ()))
        for j in range(q - 1, -1, -1):
            out.append(("cp", (j, q), (math.pi / 2 ** (q - j),)))
    if cfg["circuit_args"].get("swaps", True):
        for q in range(n // 2):
            out.append(("swap", (q, n - 1 - q), ()))
    return out
