"""QAOA MaxCut (QASMBench ``qaoa`` family): H on every qubit, then per
layer l a ZZ rotation by gamma_l on every edge and an X rotation by
beta_l on every qubit.  The graph's edges are listed in the
configuration file; the seed draws each job's angles.
"""
from __future__ import annotations


def program_circuit(cfg: dict):
    """The parameterized circuit as the system under test builds it."""
    from repro.core.library import qaoa_template
    return qaoa_template(cfg["n_qubits"], **cfg["circuit_args"])


def draw(cfg: dict, rng) -> dict:
    """One binding: gamma_l and beta_l uniform over the configured
    ranges, layer by layer."""
    lo_g, hi_g = cfg["draw"]["gamma"]
    lo_b, hi_b = cfg["draw"]["beta"]
    out = {}
    for layer in range(cfg["circuit_args"]["layers"]):
        out[f"gamma{layer}"] = float(rng.uniform(lo_g, hi_g))
        out[f"beta{layer}"] = float(rng.uniform(lo_b, hi_b))
    return out


def gates(cfg: dict, params: dict) -> list:
    n = cfg["n_qubits"]
    out = [("h", (q,), ()) for q in range(n)]
    for layer in range(cfg["circuit_args"]["layers"]):
        g, b = params[f"gamma{layer}"], params[f"beta{layer}"]
        out += [("rzz", (a, c), (g,)) for a, c in cfg["edges"]]
        out += [("rx", (q,), (b,)) for q in range(n)]
    return out
