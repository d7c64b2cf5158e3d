#!/usr/bin/env python3
"""Run the compressed simulator on a TPU chip and check what it computes.

    python chip_smoke.py            # one chip: phases 1-3 below
    python chip_smoke.py --chips4   # four chips: the sharded phase only

One chip drives the main path through its user entry points, at a state
size users of a compressed state-vector simulator call real:

1. ``qft`` at n = 28 (a 2 GiB complex64 state; the planner picks 2^20-
   amplitude blocks and 2^22-amplitude groups, 32 stages) through
   ``Simulator`` with the device codec and the Pallas kernels, then its
   first 3 stages through the host codec.
2. ``qaoa`` at n = 26 with the device codec, its first layer: the poorly
   compressible end (about 4.2x), where the RAW-escape and pressure
   paths do work.
3. Four jobs over two circuit structures at n = 20 through ``SimService``.

Depth cuts keep the width.  The host codec quantizes and zlib-compresses
the raw 2 GiB state on the host at every stage, and qaoa's incompressible
codes make each of its stages host-bound; whole runs of both would not
fit the check's time limit.

Every final state is compared, block by block, with ``simulate_dense``
run on the same chip at HIGHEST matmul precision: fidelity
|<oracle|sim>| / (|oracle| |sim|) and the norm |sim| must both reach
0.99 (the norm within 0.01 of 1).  ``--chips4`` runs the multi-device tier instead: a block-sharded
qft-28 and a lane-sharded ``qaoa_template`` batch (K = 8, n = 24), each
on four chips against the same work on one chip.

The first line names the platform, device kind and device count; without
a TPU the script stops there with a non-zero exit.  The last line is one
JSON object, printed only when every phase passed.  Compile seconds are
JAX's own backend-compile durations (set-up, counted apart from run
seconds); the persistent compilation cache lives where
``repro.launch.cache`` puts it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

#: pass bar for fidelity and for the norm of the simulated state
MIN_FIDELITY = 0.99

#: qft-28 through the host codec ships the raw 2 GiB state across the
#: boundary and runs pwrel + zlib on the host for all 256 blocks every
#: stage: the first 4 gates (3 of 32 stages) at the full width
QFT_HOST_GATES = 4
#: qft-28 on four chips against one: the first 30 gates (8 stages)
QFT_SHARDED_GATES = 30


def qaoa_first_layer_gates(n: int) -> int:
    """Gates of ``qaoa(n)`` up to the end of its first layer: the H wall,
    one ZZ per edge and one RX per qubit."""
    from repro.core.library import maxcut_edges
    return 2 * n + len(maxcut_edges(n))


class CompileClock:
    """Sums JAX's backend-compile durations (persistent-cache retrievals
    included) while active: the set-up share of a phase."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0

    def _listen(self, event: str, duration: float, **_) -> None:
        if event == self._EVENT:
            self.seconds += duration

    def __enter__(self) -> "CompileClock":
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc) -> None:
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._listen)


def oracle_state(circuit) -> np.ndarray:
    """``simulate_dense`` on the default device, fetched to the host."""
    from repro.core.dense_engine import simulate_dense
    return np.asarray(simulate_dense(circuit))


def compare_blocks(result, oracle: np.ndarray, block: int) -> dict:
    """Fidelity and norm of ``result``'s state against ``oracle``, one
    decoded block at a time (float64 sums)."""
    dot, ss, oo = 0j, 0.0, 0.0
    for lo in range(0, oracle.size, block):
        s = result.amplitudes(np.arange(lo, lo + block)).astype(np.complex128)
        o = oracle[lo:lo + block].astype(np.complex128)
        dot += np.vdot(o, s)
        ss += float(np.vdot(s, s).real)
        oo += float(np.vdot(o, o).real)
    return {"fidelity": float(abs(dot) / np.sqrt(ss * oo)),
            "norm": float(np.sqrt(ss))}


def _passed(m: dict) -> bool:
    return (m["fidelity"] >= MIN_FIDELITY and m["norm"] >= MIN_FIDELITY
            and m["norm"] <= 2 - MIN_FIDELITY)


def phase_simulator(name: str, n: int, codec: str,
                    gates: int | None = None) -> dict:
    """One circuit through ``Simulator`` with the Pallas kernels on,
    checked against the dense oracle.  ``gates`` cuts the circuit to a
    prefix of its gate list (same width, less depth)."""
    from repro.core import Circuit, EngineConfig, Simulator, build_circuit
    qc = build_circuit(name, n)
    if gates is not None:
        qc = Circuit(n, qc.gates[:gates])
    cfg = EngineConfig(codec_backend=codec, use_kernel=True)
    with CompileClock() as clock:
        t0 = time.perf_counter()
        with Simulator(qc, cfg) as sim:
            result = sim.run()
            wall = time.perf_counter() - t0
            compile_s = clock.seconds
            st = sim.stats
            m = compare_blocks(result, oracle_state(qc), 1 << sim.local_bits)
    m.update(circuit=name, n=n, codec=codec, gates=len(qc.gates),
             stages=st.n_stages, local_bits=sim.local_bits,
             compile_s=compile_s, run_s=wall - compile_s,
             h2d_bytes=st.h2d_bytes, d2h_bytes=st.d2h_bytes,
             stored_bytes_per_amp=st.bytes_per_amp_measured)
    m["ok"] = bool(_passed(m))
    return m


def _fidelity(a, b) -> float:
    a = np.asarray(a, np.complex128)
    b = np.asarray(b, np.complex128)
    return float(abs(np.vdot(a, b)) / np.sqrt(np.vdot(a, a).real
                                              * np.vdot(b, b).real))


def phase_service(n: int) -> dict:
    """Four jobs over two structures through ``SimService``; each job's
    state is checked against the oracle of its bound circuit."""
    from repro.core import EngineConfig, SimService, build_circuit
    from repro.core.library import qaoa_template
    template = qaoa_template(n)
    jobs = [(template, {"gamma0": 0.4, "beta0": 0.7}),
            (template, {"gamma0": 1.1, "beta0": 0.3}),
            (build_circuit("qft", n), None),
            (build_circuit("qft", n), None)]
    out = {"jobs": len(jobs), "done": 0, "fidelities": [], "norms": []}
    with CompileClock() as clock:
        t0 = time.perf_counter()
        with SimService(8 << 30, config=EngineConfig(
                codec_backend="device")) as svc:
            handles = [svc.submit(qc, params, readout=lambda v: v.statevector(
                force=True)) for qc, params in jobs]
            svc.drain()
        wall = time.perf_counter() - t0
    for (qc, params), job in zip(jobs, handles):
        if job.state != "done":
            out.setdefault("errors", []).append(f"job {job.job_id}: "
                                                f"{job.state} {job.error}")
            continue
        out["done"] += 1
        bound = qc.bind(params) if params else qc
        s = job.result["readout"].astype(np.complex128)
        o = oracle_state(bound).astype(np.complex128)
        out["fidelities"].append(_fidelity(o, s))
        out["norms"].append(float(np.linalg.norm(s)))
    out.update(compile_s=clock.seconds, run_s=wall - clock.seconds,
               merged_jobs=svc.stats.n_merged_jobs)
    out["ok"] = bool(out["done"] == len(jobs)
                 and min(out["fidelities"]) >= MIN_FIDELITY
                 and all(abs(x - 1) <= 1 - MIN_FIDELITY for x in out["norms"]))
    return out





def _rows(sim, devices) -> list[int]:
    """SV-group rows each of ``devices`` ran during the session."""
    return [sim.stats.rows_per_device.get(d.id, 0) for d in devices]


def phase_sharded(devices: list, n_block: int, n_lanes_qubits: int,
                  lanes: int, gates: int | None = None) -> dict:
    """The multi-device tier on ``devices`` against the same work on
    ``devices[0]`` alone: a block-sharded single state (``qft``) and a
    lane-sharded ``qaoa_template`` batch."""
    from repro.core import Circuit, EngineConfig, Simulator, build_circuit
    from repro.core.library import qaoa_template
    out: dict = {"devices": len(devices)}
    qc = build_circuit("qft", n_block)
    if gates is not None:
        qc = Circuit(n_block, qc.gates[:gates])
    states = {}
    for tag, devs in (("mesh", devices), ("one", devices[:1])):
        cfg = EngineConfig(codec_backend="device", devices=list(devs))
        with CompileClock() as clock, Simulator(qc, cfg) as sim:
            t0 = time.perf_counter()
            result = sim.run()
            out[f"block_{tag}_run_s"] = time.perf_counter() - t0 \
                - clock.seconds
            out[f"block_{tag}_compile_s"] = clock.seconds
            out[f"block_{tag}_handoffs"] = sim.stats.n_exchanged_blocks
            out[f"block_{tag}_exchange_bytes"] = sim.stats.exchange_bytes
            out[f"block_{tag}_rows"] = _rows(sim, devs)
            states[tag] = result.statevector(force=True)
    out["block_fidelity"] = _fidelity(states["one"], states["mesh"])
    params = [{"gamma0": 0.1 + 0.1 * j, "beta0": 0.9 - 0.05 * j}
              for j in range(lanes)]
    template = qaoa_template(n_lanes_qubits)
    batches = {}
    for tag, devs in (("mesh", devices), ("one", devices[:1])):
        cfg = EngineConfig(codec_backend="device", devices=list(devs),
                           batch=lanes)
        with CompileClock() as clock, Simulator(template, cfg) as sim:
            t0 = time.perf_counter()
            batch = sim.run_batch(params)
            out[f"lanes_{tag}_run_s"] = time.perf_counter() - t0 \
                - clock.seconds
            out[f"lanes_{tag}_compile_s"] = clock.seconds
            out[f"lanes_{tag}_handoffs"] = sim.stats.n_exchanged_blocks
            out[f"lanes_{tag}_rows"] = _rows(sim, devs)
            batches[tag] = [batch[j].statevector(force=True)
                            for j in range(lanes)]
    out["lane_fidelity_min"] = min(
        _fidelity(a, b) for a, b in zip(batches["one"], batches["mesh"]))
    out["ok"] = bool(out["block_mesh_handoffs"] > 0
                 and out["lanes_mesh_handoffs"] == 0
                 and min(out["block_mesh_rows"]) > 0
                 and min(out["lanes_mesh_rows"]) > 0
                 and out["block_fidelity"] >= MIN_FIDELITY
                 and out["lane_fidelity_min"] >= MIN_FIDELITY)
    return out


def _show(tag: str, m: dict) -> None:
    print(f"[chip_smoke] {tag}: " + " ".join(
        f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}"
        for k, v in m.items()), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips4", action="store_true",
                    help="run the block- and lane-sharded phase on four "
                         "chips (and the same work on one), nothing else")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(f"[chip_smoke] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("[chip_smoke] no TPU present: this check runs only on a TPU "
              "chip", file=sys.stderr)
        return 2
    want = 4 if args.chips4 else 1
    if len(devices) < want:
        print(f"[chip_smoke] --chips4 needs 4 chips, found {len(devices)}",
              file=sys.stderr)
        return 2

    from repro.launch.cache import use_compile_cache
    print(f"[chip_smoke] compile cache: {use_compile_cache()}", flush=True)

    ok = True
    if args.chips4:
        print(f"[chip_smoke] qft-28 cut to its first {QFT_SHARDED_GATES} "
              "gates (same width)", flush=True)
        m = phase_sharded(devices[:4], 28, 24, 8, gates=QFT_SHARDED_GATES)
        _show("sharded", m)
        ok = m["ok"]
    else:
        qft = phase_simulator("qft", 28, "device")
        _show("qft-28 device codec", qft)
        print(f"[chip_smoke] host codec: qft-28 cut to its first "
              f"{QFT_HOST_GATES} gates (same width)", flush=True)
        host = phase_simulator("qft", 28, "host", gates=QFT_HOST_GATES)
        _show("qft-28 host codec", host)
        layer = qaoa_first_layer_gates(26)
        print(f"[chip_smoke] qaoa-26 cut to its first layer, {layer} gates "
              "(same width)", flush=True)
        qaoa = phase_simulator("qaoa", 26, "device", gates=layer)
        _show("qaoa-26 device codec", qaoa)
        svc = phase_service(20)
        _show("service n=20", svc)
        ok = all(m["ok"] for m in (qft, host, qaoa, svc))
    if not ok:
        print("[chip_smoke] FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
