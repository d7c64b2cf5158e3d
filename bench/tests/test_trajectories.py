"""A cell in ``trajectories`` mode, driven on the CPU: a toy noisy-Ising
family run as K noise trajectories a job, each lane checked against the
reference realized from its own seed.

The toy cell is made of files and entries only, laid out as a later
cell's would be (``data/trajectories``: a family module, a configuration
and a traffic mix); the harness, the reference and ``control.py`` find
them by name.
"""
import json
import shutil
import time
from pathlib import Path

import jax
import numpy as np
import pytest

import harness
from harness import ProgramRunner, load_cell, run_cell

DATA = Path(__file__).resolve().parent / "data" / "trajectories"
NAME = "ising12.traj4"
SEED = 2 ** 31 + 11


@pytest.fixture
def toy(tmp_path, monkeypatch):
    """The toy cell's files in a checkout of their own, and the
    benchmark's entries that name them."""
    bench_dir = tmp_path / "bench"
    shutil.copytree(DATA, bench_dir)
    (bench_dir / "metrics").symlink_to(harness.BENCH_DIR / "metrics")
    monkeypatch.setattr(harness, "BENCH_DIR", bench_dir)
    bench = {
        "configs": [{"name": "ising12", "file": "bench/configs/ising12.json"}],
        "workloads": [{"name": NAME, "config": "ising12", "traffic": "traj4",
                       "chips": 1}],
        "end_to_end": harness.load_benchmark()["end_to_end"],
        "per_layer": [],
    }
    return bench, load_cell(bench, NAME, tmp_path)


def drive(toy, runner_factory=None, seconds=0.5, seed=SEED):
    bench, cell = toy
    runner = runner_factory(cell) if runner_factory else None
    return run_cell(cell, bench, seed, seconds, False, time.perf_counter(),
                    jax.devices(), runner=runner)


def recording(jobs):
    """A program runner that notes each job it is given."""
    def factory(cell):
        runner = ProgramRunner(cell)
        job = runner.job

        def noted(bindings):
            jobs.append(list(bindings))
            job(bindings)
        runner.job = noted
        return runner
    return factory


def test_program_is_correct(toy):
    """Each lane of the last window job matches the reference realized
    with its seed, and consecutive jobs (the warm-up's and the window's)
    carry fresh base seeds, the ones the run's seed draws."""
    jobs = []
    out = drive(toy, recording(jobs), seconds=3.0)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 2 and out["failed"] == 0
    assert set(out["metrics"]) == {"gates_per_s", "peak_B_per_amp",
                                   "setup_s"}
    assert len(jobs) == out["attempted"] + 1      # the warm-up job first
    for lanes in jobs:
        assert [t.seed for t in lanes] == [lanes[0].seed + j
                                           for j in range(4)]
    bases = [lanes[0].seed for lanes in jobs]
    assert len(set(bases)) == len(bases)
    rng = np.random.default_rng(SEED)
    assert jobs == [harness._draw_job(toy[1], rng) for _ in jobs]


def test_lanes_differ_and_the_stream_repeats(toy):
    """Consecutive jobs carry different base seeds, the lanes of a job
    realize different Paulis, and the same seed draws the same jobs."""
    _, cell = toy

    def stream(seed, k=5):
        rng = np.random.default_rng(seed)
        return [harness._draw_job(cell, rng) for _ in range(k)]
    jobs = stream(SEED)
    assert stream(SEED) == jobs
    bases = [job[0].seed for job in jobs]
    assert len(set(bases)) == len(bases)
    assert stream(SEED + 1) != jobs
    lanes = [harness.lane_gates(cell, t) for t in jobs[0]]
    assert all(len(g) == len(lanes[0]) for g in lanes)
    assert len({tuple(g) for g in lanes}) == len(lanes)


def test_lanes_realized_with_shifted_seeds_are_refused(toy):
    """Lane j realizes its channels with seed s + j + 1, not s + j."""
    class Shifted(ProgramRunner):
        def job(self, bindings):
            first = bindings[0]
            self._result = list(self.sim.run(
                first.params, trajectories=len(bindings),
                seed=first.seed + 1))
    out = drive(toy, Shifted)
    assert not out["correct"], out["checks"]


def test_noiseless_circuit_in_the_programs_place_is_refused(toy,
                                                           monkeypatch):
    from repro.core.library import ising
    _, cell = toy
    monkeypatch.setattr(cell.family, "program_circuit",
                        lambda cfg: ising(cfg["n_qubits"],
                                          **cfg["circuit_args"]))
    out = drive(toy)
    assert not out["correct"], out["checks"]


def test_half_the_lanes_left_out_is_refused(toy, monkeypatch):
    """Half of the trajectories are never run: their blocks keep
    |0...0>."""
    from repro.core.pipeline import StagePipeline
    orig = StagePipeline.run_stage

    def half(self, block_ids, fn, mats, lane_offsets=None, **kw):
        if lane_offsets is not None:
            keep = max(1, len(lane_offsets) // 2)
            lane_offsets = lane_offsets[:keep]
            mats = [m[:keep] for m in mats]
        return orig(self, block_ids, fn, mats, lane_offsets, **kw)
    monkeypatch.setattr(StagePipeline, "run_stage", half)
    out = drive(toy)
    assert not out["correct"], out["checks"]


def test_control_readings_separate(toy, capsys):
    """``control.py``'s readings on the trajectory cell: the program's
    below the limit, the looser codec's above it."""
    from control import CONTROL_B_R_FACTOR, readings
    _, cell = toy
    limit = cell.cfg["check"]["rel_l2_err"]
    runner = ProgramRunner(cell)
    program = readings(cell, runner, [SEED, SEED + 1])
    runner.close()
    runner = ProgramRunner(cell, b_r=CONTROL_B_R_FACTOR
                           * cell.cfg["engine"]["b_r"])
    control = readings(cell, runner, [SEED + 2])
    runner.close()
    assert max(r["rel_l2_err"] for r in program) < limit
    assert min(r["rel_l2_err"] for r in control) > limit
    printed = [json.loads(line) for line in
               capsys.readouterr().out.splitlines()]
    assert [r["seed"] for r in printed] == [SEED, SEED + 1, SEED + 2]

