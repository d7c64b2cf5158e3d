"""Fig. 12 + §4.3 boundary traffic + stage-compute comparison.

Four sections:

1. pipeline depth sweep (Fig. 12) on qft-14.
2. codec-backend comparison (host vs device-resident lossy codec): the
   host↔device bytes moved per stage — the quantity the device codec
   shrinks by shipping packed codes + sign bitmaps instead of raw
   complex64 group arrays.
3. stage compute, per-gate (PR-1) path vs the planes-resident
   transpose-minimizing schedule (core/schedule.py), side by side:
   engine-level ``t_compute + t_fetch`` plus warm per-stage-function
   kernel time, and the full-group transpose counts
   (``n_transposes_naive`` vs ``n_transposes_scheduled``).  The
   per-stage-function timing is also taken at a compute-bound layout
   (large ``local_bits`` — fewer, bigger groups) and at qft-18, where
   group planes outgrow the caches and elided transposes are real
   memory passes; the tiny-group qft-14/b=7 layout is dispatch-bound
   and shows the floor, not the ceiling.
4. resilience guardrail overhead: block checksums + pressure monitor on
   vs off at qft-14 with a spill-forcing RAM budget; the within-run
   ``guardrail_overhead`` ratio is gated absolutely by compare.py.
"""
import time

import numpy as np

from repro.core import EngineConfig, Simulator, build_circuit
from repro.core.engine import _stage_fn, _stage_mats
from repro.core.fusion import FusedGate, fuse_gates
from repro.core.groups import GroupLayout
from repro.core.partition import partition_circuit

from .common import emit, run_engine


def _stage_fn_time(name: str, n: int, local_bits: int, reps: int = 8):
    """Warm min-of-reps execution time of every stage's jitted group fn,
    summed over stages x groups, for both compute paths."""
    import jax.numpy as jnp

    qc = build_circuit(name, n)
    part = partition_circuit(qc, local_bits, 2)
    rng = np.random.default_rng(0)
    tot = {False: 0.0, True: 0.0}
    for st in part.stages:
        layout = GroupLayout(n, local_bits, tuple(st.inner))
        fused = fuse_gates(st.gates, 5)
        vg = [FusedGate(layout.remap_qubits(fg.qubits), fg.matrix)
              for fg in fused]
        if not vg:
            continue
        plan = tuple((fg.qubits, fg.is_diagonal) for fg in vg)
        nv = layout.b + layout.m
        base = rng.standard_normal((2, 2 ** nv)).astype(np.float32)
        for gs in (False, True):
            fn = _stage_fn(plan, nv, True, gs, True)
            mats = _stage_mats(vg, plan, gs)
            ins = [jnp.asarray(base) for _ in range(reps + 1)]
            fn(ins[0], *mats).block_until_ready()      # compile
            best = float("inf")
            for r in range(reps):                      # donated buffers
                t0 = time.perf_counter()
                fn(ins[r + 1], *mats).block_until_ready()
                best = min(best, time.perf_counter() - t0)
            tot[gs] += best * layout.n_groups
    return tot


def _depth_sweep(name: str, n: int, local_bits: int, prefix: str = "",
                 rounds: int = 8) -> dict[int, float]:
    """Warm per-depth wall clock of the wave-coalesced scheduler.

    Two measurement rules keep the ~10% overlap effect above the
    container's timing noise:

    * each depth gets one WARMUP run before timing — a new wave width
      means new stage-fn trace shapes, and charging depth>1 (but not
      depth 1, whose traces the warmup also compiled) for one-off jit
      compilation would report the old always-lose artifact instead of
      the steady-state schedule the planner's model predicts;
    * the depths are timed INTERLEAVED round-robin over live sessions
      (min over rounds), not in per-depth blocks — single-core container
      throughput drifts by tens of percent over minutes, and block
      timing folds that drift into the depth ratio.  The min needs a
      deep sample: identical runs swing ~1.6x on a noisy container, so
      fewer than ~8 rounds leaves the ratio itself noise-dominated.

    ``depth_2_speedup`` is the gated headline: sequential min / depth-2
    min from the same interleaved rounds.
    """
    best = _measure_depths(name, n, local_bits, rounds)
    for d in sorted(best):
        emit("pipeline", f"{prefix}depth_{d}_s", best[d])
        emit("pipeline", f"{prefix}depth_{d}_speedup", best[1] / best[d])
    return best


def _measure_depths(name: str, n: int, local_bits: int,
                    rounds: int = 8) -> dict[int, float]:
    qc = build_circuit(name, n)
    depths = (1, 2, 4)
    sims = {}
    try:
        for d in depths:
            sims[d] = Simulator(qc, EngineConfig(
                local_bits=local_bits, pipeline_depth=d)).__enter__()
            sims[d].run()              # warmup: compile stage/wave fns
        best = {d: float("inf") for d in depths}
        for _ in range(rounds):
            for d in depths:
                t0 = time.perf_counter()
                sims[d].run()
                best[d] = min(best[d], time.perf_counter() - t0)
    finally:
        for sim in sims.values():
            sim.__exit__(None, None, None)
    return best


def main():
    # Fig. 12 depth sweep at the paper layout and at a cache-exceeding
    # qft-18 layout; the *_speedup rows feed the compare.py gate
    _depth_sweep("qft", 14, 7)
    _depth_sweep("qft", 18, 11, prefix="qft18_")

    # codec backend: boundary bytes per stage, host vs device
    stats_by_backend = {}
    for backend in ("host", "device"):
        _, _, stats, t = run_engine("qft", 14, local_bits=7,
                                    codec_backend=backend)
        stats_by_backend[backend] = stats
        emit("pipeline", f"backend_{backend}_s", t)
        emit("pipeline", f"backend_{backend}_h2d_bytes", stats.h2d_bytes)
        emit("pipeline", f"backend_{backend}_d2h_bytes", stats.d2h_bytes)
        emit("pipeline", f"backend_{backend}_h2d_bytes_per_stage",
             stats.h2d_bytes / max(1, stats.n_stages))
        emit("pipeline", f"backend_{backend}_d2h_bytes_per_stage",
             stats.d2h_bytes / max(1, stats.n_stages))
        for i, (h2d, d2h) in enumerate(stats.per_stage_boundary_bytes):
            emit("pipeline", f"backend_{backend}_stage{i}_h2d_bytes", h2d)
            emit("pipeline", f"backend_{backend}_stage{i}_d2h_bytes", d2h)
    host, dev = stats_by_backend["host"], stats_by_backend["device"]
    emit("pipeline", "device_boundary_reduction",
         host.boundary_bytes / max(1, dev.boundary_bytes))

    # stage compute: per-gate (PR-1) vs scheduled planes path, side by side
    qc = build_circuit("qft", 14)
    for label, gs in (("pergate", False), ("scheduled", True)):
        best = (float("inf"), float("inf"))     # (compute+fetch, fetch)
        with Simulator(qc, EngineConfig(local_bits=7,
                                        gate_schedule=gs)) as sim:
            stats = sim.stats          # accumulates across the session's
            for _ in range(2):         # runs; diff per-run deltas (the
                c0 = stats.t_compute   # second reuses compiled stage fns)
                f0 = stats.t_fetch
                sim.run()
                best = min(best, (stats.t_compute + stats.t_fetch - c0 - f0,
                                  stats.t_fetch - f0))
        emit("pipeline", f"compute_{label}_s", best[0])
        emit("pipeline", f"compute_{label}_t_fetch_s", best[1])
    # the transpose counters are a property of the schedule, not the
    # executed path — emit them once
    emit("pipeline", "transposes_naive", stats.n_transposes_naive)
    emit("pipeline", "transposes_scheduled", stats.n_transposes_scheduled)
    emit("pipeline", "transpose_reduction",
         stats.n_transposes_naive / max(1, stats.n_transposes_scheduled))

    # resilience guardrails: block checksums + pressure monitor, on vs
    # off, at the paper layout with a RAM budget small enough that the
    # spill tier (where the checksums actually run) is exercised.
    # Interleaved min-of-rounds like the depth sweep; the emitted
    # guardrail_overhead ratio is within-run, so machine speed cancels
    # and compare.py gates it against an absolute ceiling.
    guard_cfgs = {
        "on": EngineConfig(local_bits=7, ram_budget_bytes=2048),
        "off": EngineConfig(local_bits=7, ram_budget_bytes=2048,
                            integrity_checks=False,
                            pressure_monitor=False),
    }
    sims = {}
    try:
        for k, c in guard_cfgs.items():
            sims[k] = Simulator(qc, c).__enter__()
            sims[k].run()              # warmup
        best = {k: float("inf") for k in sims}
        for _ in range(6):
            for k, s in sims.items():
                t0 = time.perf_counter()
                s.run()
                best[k] = min(best[k], time.perf_counter() - t0)
        assert sims["on"].stats.n_spills > 0   # the guarded path ran
    finally:
        for s in sims.values():
            s.__exit__(None, None, None)
    emit("pipeline", "guard_on_s", best["on"])
    emit("pipeline", "guard_off_s", best["off"])
    emit("pipeline", "guardrail_overhead", best["on"] / best["off"])

    # stage-fn kernel time (the compute the pipeline dispatches), at the
    # paper layout, a compute-bound qft-14 layout, and a cache-exceeding
    # qft-18 layout
    for label, (name, n, lb, reps) in {
        "qft14_b7": ("qft", 14, 7, 8),
        "qft14_b12": ("qft", 14, 12, 8),
        "qft18_b16": ("qft", 18, 16, 3),
    }.items():
        tot = _stage_fn_time(name, n, lb, reps)
        emit("pipeline", f"stagefn_{label}_pergate_s", tot[False])
        emit("pipeline", f"stagefn_{label}_scheduled_s", tot[True])
        emit("pipeline", f"stagefn_{label}_speedup", tot[False] / tot[True])


if __name__ == "__main__":
    main()
