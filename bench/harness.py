"""The benchmark's one harness: a cell's job stream, its window, its
metrics and the comparison that decides ``correct``.

Everything that belongs to one configuration, traffic mix or metric is
found by name:

* ``BENCHMARK.json`` names each configuration's file; the file names
  its circuit ``family``, whose module ``circuits/<family>.py`` builds
  the program's circuit, draws a job's binding from the seed and lists
  the textbook gates the reference applies;
* a traffic mix is ``traffic/<traffic>.json``: ``mode`` (``run``, one
  state a job; ``run_batch``, ``lanes`` states a job; ``trajectories``,
  ``lanes`` noise trajectories of one binding a job);
* a metric is ``metrics/<name>.py`` with ``read(ctx) -> float | None``.

A family module has ``program_circuit(cfg)``, ``draw(cfg, rng)`` (a
job's binding, or None) and ``gates(cfg, params)``: the reference's
``(name, qubits, args)`` list (``reference.py`` gives the gates and
their qubit order).  A family of a stochastic circuit, run in
``trajectories`` mode, takes ``gates(cfg, params, seed)`` and returns
the gates of the lane that the program realizes with that seed.  It
realizes the channels itself, from the contract ``Circuit.realize``
documents, without the program's code: ``rng =
np.random.default_rng(seed)``; one ``rng.random()`` u a channel, in
circuit order; the channel becomes the first outcome of its table
``((probability, gate), ...)`` whose running sum of probabilities,
added in table order, exceeds u, or the last outcome if none does.

A ``trajectories`` job draws the binding (``family.draw``) and then a
base seed s from the run's rng, so ``--seed`` reproduces the stream and
each job realizes fresh trajectories; it runs ``Simulator.run(params,
trajectories=lanes, seed=s)``, whose lane j draws with seed s + j.

A run, in order: set up the session and warm it with one whole job
(``setup_s``); drive jobs back to back and close the window at the first
job boundary at or after ``seconds``; read the device's memory peak;
then, untimed, run the reference over the last job's bindings and
compare every amplitude of every lane of its final state.
"""
from __future__ import annotations

import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
import traceback
from dataclasses import dataclass
from pathlib import Path

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

__all__ = ["BENCH_DIR", "ROOT", "Cell", "Trajectory", "load_benchmark",
           "load_cell", "CompileClock", "ProgramRunner", "ControlRunner",
           "compare", "lane_gates", "run_cell", "cell_metrics",
           "read_metric"]


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    cfg: dict            # the configuration's file
    traffic: dict        # the traffic mix's file
    chips: int
    family: object       # circuits/<family>.py

    @property
    def lanes(self) -> int:
        return int(self.traffic["lanes"])


@dataclass(frozen=True)
class Trajectory:
    """One lane of a ``trajectories`` job: the job's binding and the
    seed this lane's channels are realized with (the job's base seed
    plus the lane's index)."""
    params: dict | None
    seed: int


def lane_gates(cell: Cell, binding) -> list:
    """The reference's gate list for one lane of a job."""
    if isinstance(binding, Trajectory):
        return cell.family.gates(cell.cfg, binding.params, seed=binding.seed)
    return cell.family.gates(cell.cfg, binding)


def load_cell(bench: dict, name: str, root: Path = ROOT) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg = json.loads((root / entry["file"]).read_text())
    traffic = json.loads(
        (BENCH_DIR / "traffic" / f"{w['traffic']}.json").read_text())
    family = _module(BENCH_DIR / "circuits" / f"{cfg['family']}.py")
    return Cell(name, cfg, traffic, int(w["chips"]), family)


def cell_metrics(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a cell reports: its end-to-end ones, or with a trace
    its per-layer ones (an entry without ``workloads`` is in every
    cell)."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def read_metric(name: str, ctx: dict):
    return _module(BENCH_DIR / "metrics" / f"{name}.py").read(ctx)


class CompileClock:
    """Counts JAX's backend compiles (persistent-cache retrievals
    included) and sums their seconds while active."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        self.count = 0

    def _listen(self, event: str, duration: float, **_) -> None:
        if event == self._EVENT:
            self.seconds += duration
            self.count += 1

    def __enter__(self) -> "CompileClock":
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc) -> None:
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._listen)


# -- what a job runs on --------------------------------------------------------

_COUNTERS = ("t_decompress", "t_compress", "h2d_bytes", "d2h_bytes")


class ProgramRunner:
    """The system under test: one ``Simulator`` session for the cell,
    built through its normal entry, reused by every job.  ``engine``
    overrides the configuration's engine settings (the control raises
    ``b_r``)."""

    def __init__(self, cell: Cell, **engine):
        from repro.core import EngineConfig, Simulator
        self.cell = cell
        self.circuit = cell.family.program_circuit(cell.cfg)
        self.sim = Simulator(self.circuit, EngineConfig(
            **{**cell.cfg["engine"], **engine}))
        self.local_bits = self.sim.local_bits
        self._result = None

    def job(self, bindings: list) -> None:
        mode = self.cell.traffic["mode"]
        if mode == "run":
            self._result = [self.sim.run(bindings[0])]
        elif mode == "trajectories":
            first = bindings[0]
            self._result = list(self.sim.run(
                first.params, trajectories=len(bindings), seed=first.seed))
        else:
            self._result = list(self.sim.run_batch(bindings))

    def lane_block(self, lane: int, lo: int, hi: int) -> np.ndarray:
        """Amplitudes ``lo`` to ``hi`` of a lane of the last job's final
        state, decoded from the compressed store."""
        amps = self._result[lane].amplitudes(np.arange(lo, hi))
        return amps.astype(np.complex128)

    def counters(self) -> dict:
        st = self.sim.stats
        return {k: getattr(st, k) for k in _COUNTERS}

    def plan_stages(self, bindings: list):
        """``(fused, m, n_groups)`` of each stage the job runs."""
        first = bindings[0]
        params = first.params if isinstance(first, Trajectory) else first
        plan = self.sim.compile(params, verify=False)
        return [(sp.plan, sp.layout.m, sp.n_groups) for sp in plan.stages]

    def stats(self):
        return self.sim.stats

    def close(self) -> None:
        self.sim.close()


class ControlRunner:
    """The reference in the program's place, computed in ``dtype``: with
    bfloat16 it is the control that the comparison must refuse."""

    def __init__(self, cell: Cell, dtype):
        self.cell = cell
        self.dtype = dtype
        self.n = cell.cfg["n_qubits"]
        self._states = []

    def job(self, bindings: list) -> None:
        from reference import simulate
        self._states = [simulate(self.n, lane_gates(self.cell, b),
                                 self.dtype) for b in bindings]
        for s in self._states:
            s.re.block_until_ready()

    def lane_block(self, lane: int, lo: int, hi: int) -> np.ndarray:
        return self._states[lane].block(lo, hi)

    def close(self) -> None:
        self._states = []


# -- the comparison ------------------------------------------------------------

def compare(runner, cell: Cell, bindings: list, block_bits: int = 20) -> dict:
    """Run the float32 reference over each lane's binding and compare
    the runner's final state with it, every amplitude, a block of
    ``2^block_bits`` at a time in float64.  Returns the numbers compared
    (the worst lane) and what is shown beside them."""
    import jax.numpy as jnp
    from reference import simulate
    n = cell.cfg["n_qubits"]
    blk = 1 << min(block_bits, n)
    worst = {"rel_l2_err": 0.0, "fidelity": 1.0, "norm_dev": 0.0}
    for lane, b in enumerate(bindings):
        ref = simulate(n, lane_gates(cell, b), jnp.float32)
        d2 = r2 = s2 = 0.0
        dot = 0j
        for lo in range(0, 1 << n, blk):
            r = ref.block(lo, lo + blk)
            s = runner.lane_block(lane, lo, lo + blk)
            d2 += float(np.sum(np.abs(s - r) ** 2))
            r2 += float(np.sum(np.abs(r) ** 2))
            s2 += float(np.sum(np.abs(s) ** 2))
            dot += complex(np.vdot(r, s))
        del ref
        rel = math.sqrt(d2 / r2)
        fid = abs(dot) / math.sqrt(r2 * s2) if s2 > 0 else 0.0
        worst["rel_l2_err"] = max(worst["rel_l2_err"], rel)
        worst["fidelity"] = min(worst["fidelity"], fid)
        worst["norm_dev"] = max(worst["norm_dev"], abs(math.sqrt(s2) - 1))
    return worst


# -- one run -------------------------------------------------------------------

def _log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _draw_job(cell: Cell, rng) -> list:
    """One job's bindings, one a lane.  With ``perturb`` c in the traffic
    mix the job is SPSA's two-point evaluation: one binding theta drawn
    by the family, and the lanes theta + c * delta and theta - c * delta
    for a random sign vector delta.  In ``trajectories`` mode the lanes
    share one binding and take the seeds s, s + 1, ... from a base seed
    s drawn after it."""
    if cell.traffic["mode"] == "trajectories":
        theta = cell.family.draw(cell.cfg, rng)
        base = int(rng.integers(0, 2 ** 31))
        return [Trajectory(theta, base + j) for j in range(cell.lanes)]
    c = cell.traffic.get("perturb")
    if c is None:
        return [cell.family.draw(cell.cfg, rng) for _ in range(cell.lanes)]
    theta = cell.family.draw(cell.cfg, rng)
    keys = sorted(theta)
    delta = rng.choice((-1.0, 1.0), size=len(keys))
    return [{k: theta[k] + sign * c * d for k, d in zip(keys, delta)}
            for sign in (1.0, -1.0)]


def _memory_peak(devices) -> int | None:
    peaks = []
    for d in devices:
        ms = d.memory_stats()
        if ms and "peak_bytes_in_use" in ms:
            peaks.append(int(ms["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_cell(cell: Cell, bench: dict, seed: int, seconds: float,
             trace: bool, t_start: float, devices, runner=None) -> dict:
    """One run of ``cell``; returns the result line's object.

    ``t_start`` is when the process began (``setup_s`` counts from it);
    ``runner`` replaces the program (the tests pass a control or a
    broken program through it).
    """
    import jax
    rng = np.random.default_rng(seed)
    failed = 0
    with CompileClock() as setup_clock:
        if runner is None:
            runner = ProgramRunner(cell)
        warm = _draw_job(cell, rng)
        runner.job(warm)
    _log(f"set-up compiles: {setup_clock.count} "
         f"({setup_clock.seconds:.6f} s)")

    log_dir = None
    if trace:
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(log_dir)
    before = runner.counters() if hasattr(runner, "counters") else None
    jobs = []
    with CompileClock() as window_clock:
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        with jax.profiler.TraceAnnotation("bench.window"):
            while True:
                bindings = _draw_job(cell, rng)
                try:
                    with jax.profiler.TraceAnnotation(
                            f"bench.job{len(jobs)}"):
                        runner.job(bindings)
                except Exception:  # a job that fails ends the window
                    traceback.print_exc()
                    failed += 1
                    break
                jobs.append(bindings)
                if time.perf_counter() - t0 >= seconds:
                    break
        window_s = time.perf_counter() - t0
    after = runner.counters() if hasattr(runner, "counters") else None
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        import trace_reduce as trace_mod
        tr = trace_mod.load(trace_mod.find_xplane(log_dir))
        win = [s for s in tr.host if s.name == "bench.window"]
        reduced = trace_mod.reduce(tr, win[0].start_ns, win[0].end_ns)
        shutil.rmtree(log_dir, ignore_errors=True)
    _log(f"window: {len(jobs)} jobs in {window_s:.6f} s, compiles inside "
         f"it: {window_clock.count} ({window_clock.seconds:.6f} s)")
    memory_peak = _memory_peak(devices[:cell.chips])

    ctx = {"cell": cell, "seed": seed, "jobs": len(jobs),
           "window_s": window_s, "setup_s": setup_s,
           "lanes": cell.lanes, "n": cell.cfg["n_qubits"],
           "trace": reduced}
    if before is not None and jobs:
        from work import job_work, peaks_for
        ctx["delta"] = {k: after[k] - before[k] for k in before}
        st = runner.stats()
        ctx["n_gates"] = st.n_gates
        ctx["n_stages"] = st.n_stages
        ctx["peak_total_bytes"] = st.peak_total_bytes
        peaks = peaks_for(devices[0].device_kind) if trace else None
        if peaks is not None:
            ctx["work"] = job_work(runner.plan_stages(jobs[-1]),
                                   runner.local_bits, cell.lanes, peaks)
            _log(f"roofline bounds: gates {ctx['work'].gates.bound}, "
                 f"codec {ctx['work'].codec.bound}")
    metrics = {}
    for m in cell_metrics(bench, cell.name, trace):
        v = read_metric(m["name"], ctx) if jobs else None
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    limits = cell.cfg["check"]
    t_cmp = time.perf_counter()
    if jobs and not failed:
        found = compare(runner, cell, jobs[-1])
    else:   # a failed job leaves no state to compare
        found = {k: float("nan") for k in limits}
    runner.close()
    _log(f"comparison: {time.perf_counter() - t_cmp:.6f} s")
    for k, v in found.items():
        if k not in limits:
            _log(f"shown: {k}={v!r}")
    checks = {}
    for k, limit in limits.items():
        v = found[k]
        checks[k] = {"value": v, "limit": limit}
    correct = bool(failed == 0 and jobs and all(
        c["limit"] is not None and c["value"] <= c["limit"]
        for c in checks.values()))
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    out = {"correct": correct, "attempted": len(jobs) + failed,
           "failed": failed, "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = checks
    for k, c in checks.items():
        print(f"check {k}={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr, flush=True)
    return out
