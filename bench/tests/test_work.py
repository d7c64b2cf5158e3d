"""Work counted per stage and per codec call, against plans the program
builds on the CPU and the counters of a small run."""
import numpy as np
import pytest

import work
from harness import ProgramRunner, _draw_job, load_benchmark, load_cell

PEAKS = work.Peaks("test", flops_per_s=1e12, hbm_bytes_per_s=1e11,
                   hbm_bytes=1e10, source="test")


def test_least_seconds_picks_the_larger_bound():
    w = work.least_seconds(2e12, 1e10, PEAKS)        # 2 s of flops, 0.1 s
    assert w.least_s == pytest.approx(2.0) and w.bound == "flops"
    w = work.least_seconds(1e9, 1e11, PEAKS)         # 1 ms, 1 s of bytes
    assert w.least_s == pytest.approx(1.0) and w.bound == "bytes"
    both = w + work.least_seconds(2e12, 0, PEAKS)
    assert both.least_s == pytest.approx(3.0)


def test_stage_and_codec_counts():
    # two fused gates on a 2^10 group: a dense 3-qubit one, a diagonal one
    w = work.stage_work([((0, 1, 2), False), ((3, 4), True)], 10, PEAKS)
    assert w.flops == (8 * 8 + 6) * 1024
    assert w.bytes == 16 * 1024
    assert work.wire_bytes(1 << 20) == 2 * (2 * (1 << 20) + (1 << 17) + 4)
    assert work.codec_work(1 << 20, PEAKS).bytes == \
        8 * (1 << 20) + work.wire_bytes(1 << 20)


def test_peaks_table():
    p = work.peaks_for("TPU v5 lite")
    assert p.flops_per_s == 197e12 and p.hbm_bytes_per_s == 819e9
    with pytest.raises(KeyError):
        work.peaks_for("cpu")


@pytest.mark.parametrize("name,stages,rows", [("qft26.single", 20, 320),
                                              ("qaoa24.single", 14, 56)])
def test_job_work_of_the_full_size_plans(name, stages, rows):
    from repro.core import EngineConfig, Simulator
    cell = load_cell(load_benchmark(), name)
    qc = cell.family.program_circuit(cell.cfg)
    with Simulator(qc, EngineConfig(**cell.cfg["engine"])) as sim:
        b = _draw_job(cell, np.random.default_rng(0))
        plan = sim.compile(b[0], verify=False)
        jw = work.job_work([(sp.plan, sp.layout.m, sp.n_groups)
                            for sp in plan.stages], plan.local_bits,
                           cell.lanes, PEAKS)
    assert (plan.local_bits, jw.stages, jw.stage_rows) == (20, stages, rows)
    # every amplitude is decoded and encoded once a stage
    assert jw.codec_blocks == 2 * stages * 2 ** (cell.cfg["n_qubits"] - 20)
    assert jw.gates.bytes == 16 * stages * 2 ** cell.cfg["n_qubits"]


def test_codec_counts_match_the_program_counters():
    from repro.core.library import maxcut_edges
    cell = load_cell(load_benchmark(), "qaoa24.single")
    n = 12
    cell.cfg["n_qubits"] = n
    cell.cfg["edges"] = [list(e) for e in maxcut_edges(n)]
    runner = ProgramRunner(cell)
    try:
        b = _draw_job(cell, np.random.default_rng(1))
        runner.job(b)
        before = runner.counters()
        runner.job(b)
        after = runner.counters()
        jw = work.job_work(runner.plan_stages(b), runner.local_bits, 1,
                           PEAKS)
        moved = (after["h2d_bytes"] - before["h2d_bytes"]
                 + after["d2h_bytes"] - before["d2h_bytes"])
        assert moved == jw.codec_blocks * work.wire_bytes(
            1 << runner.local_bits)
    finally:
        runner.close()
