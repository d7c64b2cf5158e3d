"""Fig. 13: multi-device scaling on a virtual mesh (subprocess with
forced host device counts, like the paper's 1/2/4 GPUs).

Two sharding modes, each swept over 1/2/4/8 virtual devices:

* ``lanes_{d}_*``   — lane-sharded batch (qft-18, K=8 lanes): each device
  runs its contiguous lane slice, zero cross-device exchange.
* ``devices_{d}_*`` — block-sharded single state (qft-18): SV groups are
  placed round-robin on the mesh and only *encoded* wire crosses device
  boundaries at stage hand-offs.

Each measurement runs in a fresh subprocess (the device count is an XLA
startup flag) that prints one machine-readable ``BMQSIM_RESULT {json}``
line; the driver checks the exit code and surfaces stderr instead of
crashing on ``float(stdout.split(...))``.  On a single-core container
the recorded speedups are honest ~1.0x — the row exists so a real
multi-core runner records scaling and compare.py gates it from then on.

Virtual devices exist only on the CPU backend, so this bench runs only
under ``JAX_PLATFORMS=cpu`` and says so otherwise; real chips are
measured in one process by ``chip_smoke.py --chips4``.

``BMQSIM_MULTIDEV_SMOKE=1`` shrinks the sweep (qft-12, K=4, 1/2 devices,
``smoke_``-prefixed keys) so CI can exercise the harness in seconds.
"""
import json
import os
import subprocess
import sys
import textwrap

from .common import emit

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TAG = "BMQSIM_RESULT "

_CODE = """
import json, time, jax
import numpy as np
from repro.core import (EngineConfig, Simulator, build_circuit, fidelity,
                        simulate_dense)

mode, n, k, b = {mode!r}, {n}, {k}, {b}
qc = build_circuit("qft", n)
cfg = EngineConfig(local_bits=b, mesh_shape=len(jax.devices()),
                   batch=k if mode == "lanes" else 1)
out = {{"devices": len(jax.devices())}}
t0 = time.perf_counter()
with Simulator(qc, cfg) as sim:
    if mode == "lanes":
        sim.run(trajectories=k)
        out["t"] = time.perf_counter() - t0
    else:
        result = sim.run()
        out["t"] = time.perf_counter() - t0
        ideal = np.asarray(simulate_dense(qc)).astype(np.complex128)
        out["fidelity"] = float(fidelity(
            ideal, result.statevector().astype(np.complex128)))
    out["exchange_bytes"] = sim.stats.exchange_bytes
    out["n_exchanged_blocks"] = sim.stats.n_exchanged_blocks
print({tag!r} + json.dumps(out))
"""


def _run_one(mode: str, ndev: int, n: int, k: int, b: int) -> dict:
    """One measurement in a subprocess with ``ndev`` forced host devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={ndev}"
                        ).strip()
    env["PYTHONPATH"] = "src"
    code = textwrap.dedent(_CODE).format(mode=mode, n=n, k=k, b=b, tag=_TAG)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=3600, cwd=_ROOT)
    if proc.returncode != 0:
        raise RuntimeError(
            f"multidev subprocess (mode={mode} devices={ndev}) exited "
            f"{proc.returncode}; stderr tail:\n{proc.stderr[-4000:]}")
    for line in reversed(proc.stdout.splitlines()):
        if line.startswith(_TAG):
            return json.loads(line[len(_TAG):])
    raise RuntimeError(
        f"multidev subprocess (mode={mode} devices={ndev}) printed no "
        f"{_TAG!r} line; stdout tail:\n{proc.stdout[-2000:]}\n"
        f"stderr tail:\n{proc.stderr[-2000:]}")


def main():
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        raise RuntimeError(
            "bench_multidev sweeps virtual CPU devices and needs "
            "JAX_PLATFORMS=cpu; run chip_smoke.py --chips4 for real chips")
    smoke = os.environ.get("BMQSIM_MULTIDEV_SMOKE") == "1"
    pre = "smoke_" if smoke else ""
    n, k, b = (12, 4, 8) if smoke else (18, 8, 12)
    sweep = (1, 2) if smoke else (1, 2, 4, 8)

    base = None
    for ndev in sweep:
        r = _run_one("lanes", ndev, n, k, b)
        base = base or r["t"]
        emit("multidev", f"{pre}lanes_{ndev}_s", r["t"])
        emit("multidev", f"{pre}lanes_{ndev}_speedup", base / r["t"])

    base = None
    for ndev in sweep:
        r = _run_one("block", ndev, n, k, b)
        base = base or r["t"]
        emit("multidev", f"{pre}devices_{ndev}_s", r["t"])
        emit("multidev", f"{pre}devices_{ndev}_speedup", base / r["t"])
    # last sweep entry is the widest mesh: record its readout fidelity and
    # how much smaller the encoded exchange wire is than raw block bytes
    emit("multidev", f"{pre}blockshard_fidelity", r["fidelity"])
    if r["fidelity"] < 0.99:
        raise RuntimeError(
            f"block-sharded fidelity {r['fidelity']:.6f} < 0.99 on "
            f"{sweep[-1]} devices")
    if r["n_exchanged_blocks"]:
        raw = r["n_exchanged_blocks"] * (1 << b) * 8   # complex64 blocks
        emit("multidev", f"{pre}exchange_compression_speedup",
             raw / r["exchange_bytes"])


if __name__ == "__main__":
    main()
