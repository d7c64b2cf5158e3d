"""Trotterized transverse-field Ising chain under depolarizing noise:
H on every qubit, then per layer l a ZZ rotation by 2 * 0.1 * J_l on
each neighbouring pair (q, q + 1) and an X rotation by 2 * 0.1 * h_l on
each qubit; after every gate a depolarizing channel (I with probability
1 - p, X, Y, Z with p / 3 each) on each of its qubits, in the gate's
qubit order.  The couplings J_l, h_l are listed in the configuration
file.  Nothing is drawn per job but the trajectories' seeds.
"""
from __future__ import annotations

import numpy as np


def program_circuit(cfg: dict):
    """The stochastic circuit as the system under test builds it."""
    from repro.core.library import ising, with_depolarizing
    return with_depolarizing(ising(cfg["n_qubits"], **cfg["circuit_args"]),
                             cfg["depolarizing_p"])


def draw(cfg: dict, rng) -> None:
    return None


def gates(cfg: dict, params: None, seed: int) -> list:
    """The textbook gates of the trajectory realized with ``seed``: one
    ``rng.random()`` a channel, in circuit order, picks the first
    outcome whose running sum of probabilities exceeds it."""
    n, p = cfg["n_qubits"], cfg["depolarizing_p"]
    table = ((1.0 - p, "i"), (p / 3.0, "x"), (p / 3.0, "y"), (p / 3.0, "z"))
    rng = np.random.default_rng(seed)
    out = []

    def noisy(name, qubits, args=()):
        out.append((name, qubits, args))
        for q in qubits:
            u = float(rng.random())
            acc, picked = 0.0, table[-1][1]
            for prob, pauli in table:
                acc += prob
                if u < acc:
                    picked = pauli
                    break
            out.append((picked, (q,), ()))

    for q in range(n):
        noisy("h", (q,))
    for coupling, field in cfg["couplings"]:
        for q in range(n - 1):
            noisy("rzz", (q, q + 1), (2.0 * coupling * 0.1,))
        for q in range(n):
            noisy("rx", (q,), (2.0 * field * 0.1,))
    return out
