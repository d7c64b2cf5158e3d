"""Each cell's run, driven on the CPU at a size a test run holds: the
program passes its comparison; the control and each fault the cell can
have are refused.

The look for a chip is skipped (``run_cell`` is called directly); the
rest of a run is the benchmark's own: its job stream, window, metrics
and the comparison with the cell's configured limits.  A fault breaks
the timed path underneath, in the program, while the run drives it.
"""
import time

import jax
import pytest

import harness
from harness import ProgramRunner, load_benchmark, load_cell, run_cell

#: qubits of the test-size copy of each cell, and (for qft) the input
#: qubits set, scaled as the full-size cell sets its top 8 of 26
SIZES = {"qft26.single": 12, "qaoa24.single": 12, "qaoa24.sweep": 12}
CELLS = sorted(SIZES)


def tiny(name: str):
    from repro.core.library import maxcut_edges
    bench = load_benchmark()
    cell = load_cell(bench, name)
    n = SIZES[name]
    cell.cfg["n_qubits"] = n
    if "edges" in cell.cfg:
        cell.cfg["edges"] = [list(e) for e in maxcut_edges(n)]
    if "x_qubits" in cell.cfg:
        k = len(cell.cfg["x_qubits"]) * n // 26
        cell.cfg["x_qubits"] = list(range(n - k, n))
    return bench, cell


def drive(name, runner_factory=None, seconds=0.5, seed=2 ** 31 + 7):
    bench, cell = tiny(name)
    runner = runner_factory(cell) if runner_factory else None
    return run_cell(cell, bench, seed, seconds, False, time.perf_counter(),
                    jax.devices(), runner=runner)


@pytest.mark.parametrize("name", CELLS)
def test_program_is_correct(name):
    out = drive(name)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"gates_per_s", "peak_B_per_amp",
                                   "setup_s"}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
def test_control_is_refused(name):
    """The program's own lower-precision path: the codec's error bound
    ten times looser."""
    from control import CONTROL_B_R_FACTOR

    def control(cell):
        return ProgramRunner(cell, b_r=CONTROL_B_R_FACTOR
                             * cell.cfg["engine"]["b_r"])
    out = drive(name, control)
    assert not out["correct"], out["checks"]


@pytest.fixture
def fresh_stage_programs():
    """Stage programs are cached per structure across sessions: drop
    them around a fault so the broken ones neither come from nor stay in
    the cache."""
    from repro.core import engine
    for f in (engine._stage_fn, engine._stage_fn_wave,
              engine._stage_fn_batch):
        f.cache_clear()
    yield
    for f in (engine._stage_fn, engine._stage_fn_wave,
              engine._stage_fn_batch):
        f.cache_clear()


@pytest.mark.parametrize("name", CELLS)
def test_stage_returning_its_state_unchanged_is_refused(
        name, monkeypatch, fresh_stage_programs):
    from repro.core import engine
    monkeypatch.setattr(engine, "execute_schedule_batched",
                        lambda sched, planes, mats, **kw: planes)
    monkeypatch.setattr(engine, "execute_schedule",
                        lambda sched, planes, mats, **kw: planes)
    out = drive(name)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_half_the_groups_left_out_is_refused(name, monkeypatch):
    """Half of each stage's batch of groups is never run: their blocks
    keep the previous stage's amplitudes."""
    from repro.core.pipeline import StagePipeline
    orig = StagePipeline.run_stage

    def half(self, block_ids, *a, **kw):
        return orig(self, block_ids[: max(1, len(block_ids) // 2)], *a,
                    **kw)
    monkeypatch.setattr(StagePipeline, "run_stage", half)
    out = drive(name)
    assert not out["correct"], out["checks"]


def test_half_the_lanes_left_out_is_refused(monkeypatch):
    """A sweep's second lane is never run: its blocks keep |0...0>."""
    from repro.core.pipeline import StagePipeline
    orig = StagePipeline.run_stage

    def half(self, block_ids, fn, mats, lane_offsets=None, **kw):
        if lane_offsets is not None:
            keep = max(1, len(lane_offsets) // 2)
            lane_offsets = lane_offsets[:keep]
            mats = [m[:keep] for m in mats]
        return orig(self, block_ids, fn, mats, lane_offsets, **kw)
    monkeypatch.setattr(StagePipeline, "run_stage", half)
    out = drive("qaoa24.sweep")
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_answer_altered_where_it_is_produced_is_refused(name, monkeypatch):
    """The encode output of the first block of every wave comes back
    with its real plane's signs flipped."""
    import numpy as np

    from repro.compression.device_codec import PlaneWire
    from repro.core.pipeline import DeviceCodecBackend
    orig = DeviceCodecBackend.await_result_batch

    def altered(self, ticket):
        rows = orig(self, ticket)
        re, im = rows[0][0]
        flipped = PlaneWire(re.codes, np.bitwise_not(re.sign_bytes),
                            re.l_max)
        rows[0] = [(flipped, im)] + list(rows[0][1:])
        return rows
    monkeypatch.setattr(DeviceCodecBackend, "await_result_batch", altered)
    out = drive(name)
    assert not out["correct"], out["checks"]


def test_limits_are_set():
    for name in CELLS:
        _, cell = tiny(name)
        assert all(v is not None for v in cell.cfg["check"].values())
    assert harness.load_benchmark()["run_seconds"] >= 1


def test_a_failed_job_ends_the_window_and_is_refused():
    calls = []

    def failing(cell):
        runner = ProgramRunner(cell)
        job = runner.job

        def flaky(bindings):
            calls.append(1)
            if len(calls) == 2:            # the first job of the window
                raise RuntimeError("injected")
            job(bindings)
        runner.job = flaky
        return runner
    out = drive("qaoa24.single", failing)
    assert not out["correct"]
    assert (out["attempted"], out["failed"]) == (1, 1)
