"""Planner/executor split: ExecutionPlan artifacts, budget-driven
auto-tuning, plan fingerprints in checkpoints, qsim --explain."""
import numpy as np
import pytest

from repro import (EngineConfig, ExecutionPlan, Simulator, build_circuit,
                   qaoa_template, random_circuit)
from repro import spans
from repro.core.planner import estimate_bytes_per_amp, resolve_config
from repro.launch import qsim


# -- cost model ----------------------------------------------------------------

def test_bytes_per_amp_estimate_shape():
    """Conservative, monotone in b_r, never above the RAW-escape bound."""
    assert estimate_bytes_per_amp(1e-3, compression=False) == 8.0
    loose = estimate_bytes_per_amp(1e-2)
    tight = estimate_bytes_per_amp(1e-5)
    assert 0.5 < loose <= tight <= 8.0


def test_resolve_config_explicit_passthrough():
    qc = build_circuit("qft", 10)
    cfg, auto, part = resolve_config(qc, EngineConfig(local_bits=5))
    assert not auto and part is None
    assert (cfg.local_bits, cfg.inner_size, cfg.pipeline_depth) == (5, 2, 2)
    # memory budget flows into the store backstop even with explicit knobs
    cfg, _, _ = resolve_config(qc, EngineConfig(local_bits=5,
                                                memory_budget_bytes=4096))
    assert cfg.ram_budget_bytes == 4096
    # ... but never tramples an explicit ram budget
    cfg, _, _ = resolve_config(qc, EngineConfig(local_bits=5,
                                                memory_budget_bytes=4096,
                                                ram_budget_bytes=999))
    assert cfg.ram_budget_bytes == 999
    # the budget search hands back the partition it already computed
    cfg, auto, part = resolve_config(
        qc, EngineConfig(memory_budget_bytes=64 * 2 ** 10))
    assert auto and part is not None
    assert part.local_bits == cfg.local_bits


# -- pipeline depth auto-tuning off measured calibration -----------------------

def test_depth_model_fetch_dominant_picks_sequential():
    """When the blocking d2h wait dominates the phase mix, coalescing
    waves can't pay for its dispatch tax — the auto-tuner must fall back
    to depth 1 instead of reproducing the old always-2 losing choice."""
    from repro.core.planner import PipelineCalibration, predict_depth_speedup

    fetch_dom = PipelineCalibration(t_load=0.1, t_compute=0.1,
                                    t_fetch=1.0, t_store=0.1)
    assert predict_depth_speedup(2, fetch_dom) < 1.0
    qc = build_circuit("qft", 10)
    cfg, _, _ = resolve_config(qc, EngineConfig(local_bits=5),
                               calibration=fetch_dom)
    assert cfg.pipeline_depth == 1
    # budget-driven search honors the same model
    cfg, _, _ = resolve_config(
        qc, EngineConfig(memory_budget_bytes=64 * 2 ** 10),
        calibration=fetch_dom)
    assert cfg.pipeline_depth == 1


def test_depth_model_compute_dominant_picks_overlap():
    from repro.core.planner import PipelineCalibration, predict_depth_speedup

    comp_dom = PipelineCalibration(t_load=0.1, t_compute=1.0,
                                   t_fetch=0.1, t_store=0.1)
    assert predict_depth_speedup(2, comp_dom) > 1.0
    qc = build_circuit("qft", 10)
    cfg, _, _ = resolve_config(qc, EngineConfig(local_bits=5),
                               calibration=comp_dom)
    assert cfg.pipeline_depth >= 2


def test_depth_model_never_repeats_bench5_losing_choice():
    """BENCH_5 recorded depth-2 at 0.58x of sequential.  A calibration
    carrying that measured profile must drive every auto-tuned path to
    depth 1 — the planner never again selects a depth whose (measured or
    predicted) speedup is below 1."""
    from repro.core.planner import PipelineCalibration, predict_depth_speedup

    bench5 = PipelineCalibration(t_load=0.3, t_compute=0.5, t_fetch=0.2,
                                 t_store=0.3,
                                 measured=((2, 0.58), (4, 0.54), (8, 0.46)))
    assert predict_depth_speedup(2, bench5) == pytest.approx(0.58)
    qc = build_circuit("qft", 14)
    for cfg_in in (EngineConfig(local_bits=7),
                   EngineConfig(memory_budget_bytes=96 * 2 ** 10)):
        cfg, _, _ = resolve_config(qc, cfg_in, calibration=bench5)
        assert cfg.pipeline_depth == 1
    # an explicit depth is the user's call — passed through untouched
    cfg, _, _ = resolve_config(qc, EngineConfig(local_bits=7,
                                                pipeline_depth=2),
                               calibration=bench5)
    assert cfg.pipeline_depth == 2


def test_auto_depth_never_predicts_losing_speedup():
    """Whatever depth the auto-tuner lands on, its own model must rate
    that depth >= 1.0x — across a sweep of synthetic phase mixes."""
    from repro.core.planner import PipelineCalibration, predict_depth_speedup

    qc = build_circuit("qft", 10)
    mixes = [(l, c, f, s)
             for l in (0.1, 1.0) for c in (0.1, 1.0)
             for f in (0.05, 1.0) for s in (0.1, 1.0)]
    for l, c, f, s in mixes:
        cal = PipelineCalibration(t_load=l, t_compute=c, t_fetch=f, t_store=s)
        cfg, _, _ = resolve_config(qc, EngineConfig(local_bits=5),
                                   calibration=cal)
        assert predict_depth_speedup(cfg.pipeline_depth, cal) >= 1.0


def test_sim_stats_expose_pipeline_calibration():
    """A run yields the per-group-phase calibration the next plan's depth
    model consumes, and the plan artifact records its predicted overlap."""
    qc = build_circuit("qft", 10)
    with Simulator(qc, EngineConfig(local_bits=5)) as sim:
        plan = sim.compile()
        assert plan.predicted.depth_speedup > 0
        assert "overlap speedup" in plan.describe()
        rt = ExecutionPlan.from_json(plan.to_json())
        assert rt.predicted.depth_speedup == plan.predicted.depth_speedup
        sim.run()
        stats = sim.stats
    assert stats.n_group_phases > 0
    cal = stats.pipeline_calibration()
    assert cal.t_load >= 0 and cal.t_compute >= 0
    assert cal.t_fetch >= 0 and cal.t_store >= 0


# -- budget guarantee (the acceptance criterion) -------------------------------

@pytest.mark.parametrize("n,budget_kib", [(14, 96), (18, 2048)])
def test_planner_respects_budget_qft(n, budget_kib):
    """Auto-planned qft-14/qft-18 under a budget: the chosen
    (local_bits, inner_size) keeps the store's RAM peak within it, with
    no disk spill needed on the happy path."""
    budget = budget_kib * 2 ** 10
    qc = build_circuit("qft", n)
    with Simulator(qc, EngineConfig(memory_budget_bytes=budget)) as sim:
        assert sim.config.local_bits is not None
        plan = sim.compile()
        assert plan.auto_tuned
        assert plan.predicted.working_set_bytes <= budget
        sim.run()
        stats = sim.stats
    assert stats.peak_ram_bytes <= budget
    assert stats.n_spills == 0
    stored = spans.recent(stats.last_run)[-1].stored_bytes
    assert 0.0 < stored / 2 ** n <= 8.0


def test_unsatisfiable_budget_warns_and_spills():
    """A budget below any candidate's working set still runs: the
    planner warns, and the store budget backstop spills to disk while
    keeping the RAM tier within budget."""
    budget = 2000
    qc = build_circuit("qft", 10)
    with pytest.warns(RuntimeWarning, match="spill"):
        sim = Simulator(qc, EngineConfig(memory_budget_bytes=budget))
    with sim:
        sim.run()
        stats = sim.stats
    assert stats.peak_ram_bytes <= budget
    assert stats.n_spills > 0


# -- planned == explicit (property) --------------------------------------------

def test_planned_execution_state_identical_property():
    """Planned execution is state-identical to running the explicit
    config the planner chose — across random circuits and budgets."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=5, deadline=None)
    @given(n=st.integers(7, 9), seed=st.integers(0, 10 ** 6),
           budget_kib=st.sampled_from([8, 32, 128]))
    def check(n, seed, budget_kib):
        qc = random_circuit(n, 3 * n, seed=seed)
        cfg = EngineConfig(memory_budget_bytes=budget_kib * 2 ** 10)
        with Simulator(qc, cfg) as sim:
            plan = sim.compile()
            sv_auto = sim.run().statevector()
            assert sim.stats.peak_ram_bytes <= cfg.memory_budget_bytes
        explicit = EngineConfig(local_bits=plan.local_bits,
                                inner_size=plan.inner_size,
                                pipeline_depth=plan.pipeline_depth)
        with Simulator(qc, explicit) as sim:
            sv_exp = sim.run().statevector()
        assert np.array_equal(sv_auto, sv_exp)

    check()


def test_execute_from_deserialized_plan():
    """A plan survives JSON and drives a fresh session to the identical
    state — the executor honors the artifact, not its own search."""
    qc = build_circuit("qaoa", 10)
    with Simulator(qc, EngineConfig(memory_budget_bytes=64 * 2 ** 10)) as s1:
        plan = s1.compile()
        sv1 = s1.run().statevector()
    plan2 = ExecutionPlan.from_json(plan.to_json())
    assert plan2 == plan
    assert hash(plan2) == hash(plan)
    assert plan2.fingerprint == plan.fingerprint
    with Simulator(qc, EngineConfig(), plan=plan2) as s2:
        assert s2.config.local_bits == plan.local_bits
        sv2 = s2.run().statevector()
    assert np.array_equal(sv1, sv2)
    # a plan compiled for a different circuit is refused
    with pytest.raises(ValueError, match="different circuit"):
        Simulator(build_circuit("qft", 10), EngineConfig(), plan=plan2)


def test_plan_execution_adopts_every_recorded_knob():
    """'Executes it verbatim' means ALL recorded knobs — codec params
    included — override whatever the config says, so the checkpointed
    plan fingerprint always matches the artifact's."""
    qc = build_circuit("ghz_state", 8)
    src = EngineConfig(local_bits=4, b_r=1e-2, gate_schedule=False,
                       prescan=False)
    with Simulator(qc, src) as s1:
        plan = s1.compile()
    with Simulator(qc, EngineConfig(), plan=plan) as s2:
        cfg = s2.config
        assert (cfg.b_r, cfg.gate_schedule, cfg.prescan) == \
            (1e-2, False, False)
        assert s2._engine.plan_fingerprint() == plan.fingerprint
        s2.run()


def test_corrupt_plan_gate_slices_rejected():
    """A plan whose gate slices don't tile the circuit's gate list is
    refused instead of silently simulating a different circuit."""
    import dataclasses
    qc = build_circuit("qft", 8)
    with Simulator(qc, EngineConfig(local_bits=4)) as sim:
        plan = sim.compile()
    last = plan.stages[-1]
    truncated = dataclasses.replace(
        last, gate_slice=(last.gate_slice[0], last.gate_slice[1] - 1))
    bad = dataclasses.replace(plan, stages=plan.stages[:-1] + (truncated,))
    with pytest.raises(ValueError, match="covers"):
        Simulator(qc, EngineConfig(), plan=bad)


def test_compile_stamps_requested_binding():
    """The cached structural plan is re-labeled with the binding it was
    asked for, not the first one compiled."""
    with Simulator(qaoa_template(8, layers=1),
                   EngineConfig(local_bits=4)) as sim:
        p1 = sim.compile(params={"gamma0": 0.3, "beta0": 0.2})
        p2 = sim.compile(params={"gamma0": 1.0, "beta0": 0.5})
        assert dict(p1.params_key)["gamma0"] == 0.3
        assert dict(p2.params_key)["gamma0"] == 1.0
        assert p1.fingerprint == p2.fingerprint


# -- reuse contract under auto-tuning ------------------------------------------

def test_auto_sweep_compiles_once_and_resets_boundary_list():
    """An auto-planned parameter sweep compiles stage fns exactly once;
    per_stage_boundary_bytes describes the latest run only."""
    cfg = EngineConfig(memory_budget_bytes=32 * 2 ** 10)
    with Simulator(qaoa_template(10, layers=1), cfg) as sim:
        sim.run(params={"gamma0": 0.3, "beta0": 0.2})
        compiles = sim.stats.n_stagefn_compiles
        n1 = len(sim.stats.per_stage_boundary_bytes)
        sim.run(params={"gamma0": 1.0, "beta0": 0.7})
        assert sim.stats.n_stagefn_compiles == compiles
        assert len(sim.stats.per_stage_boundary_bytes) == n1


# -- the artifact itself -------------------------------------------------------

def test_plan_artifact_contents():
    qc = build_circuit("qft", 10)
    with Simulator(qc, EngineConfig(local_bits=5)) as sim:
        plan = sim.compile()
        assert plan is sim.compile()        # cached per structure
        assert plan.n_stages == sim.stats.n_stages
        assert plan.fingerprint == sim._engine.plan_fingerprint()
        # stage records: operand slots tile the gate list in order
        lo = 0
        for sp in plan.stages:
            assert sp.gate_slice[0] == lo
            lo = sp.gate_slice[1]
            assert sp.stagefn_key[0] == sp.plan
            assert sp.device_slot(0) == 0
        assert lo == len(qc.gates)
        text = plan.describe()
        assert "ExecutionPlan" in text and "local_bits=5" in text
        assert f"{plan.n_stages} stages" in text


def test_multidevice_plan_roundtrip_and_placement():
    """A multi-device plan records per-device predictions and round-robin
    group placement, survives JSON round-trip, and describe() surfaces
    the mesh; a pre-v9 dump (no per_device_peak_bytes) is backfilled."""
    import jax
    qc = build_circuit("qft", 10)
    cfg = EngineConfig(local_bits=4, devices=list(jax.devices()) * 4)
    with Simulator(qc, cfg) as sim:
        plan = sim.compile(verify=False)
        assert plan.n_devices == 4
        p = plan.predicted
        assert 0 < p.per_device_peak_bytes <= (p.peak_ram_bytes
                                               + p.pipeline_bytes)
        for sp in plan.stages:
            slots = {sp.device_slot(g) for g in range(sp.layout.n_groups)}
            assert slots <= set(range(4))
            assert sp.device_slot(5) == 5 % 4
        text = plan.describe()
        assert "devices=4" in text and "per-device peak" in text
        blob = plan.to_json()
        rt = ExecutionPlan.from_json(blob)
        assert rt.predicted.per_device_peak_bytes == p.per_device_peak_bytes
        assert rt.n_devices == 4
        # pre-v9 dump: drop the field, from_json falls back to mesh peak
        import json
        old = json.loads(blob)
        del old["predicted"]["per_device_peak_bytes"]
        legacy = ExecutionPlan.from_json(json.dumps(old))
        assert legacy.predicted.per_device_peak_bytes == (
            p.peak_ram_bytes + p.pipeline_bytes)


def test_plan_fingerprint_tracks_layout_not_execution_knobs():
    qc = build_circuit("qft", 8)
    def fp(**kw):
        with Simulator(qc, EngineConfig(**kw)) as sim:
            return sim.compile().fingerprint
    base = fp(local_bits=4)
    assert base == fp(local_bits=4, use_kernel=False, pipeline_depth=4)
    assert base != fp(local_bits=5)
    assert base != fp(local_bits=4, inner_size=3)
    assert base != fp(local_bits=4, b_r=1e-2)


# -- checkpoint integration ----------------------------------------------------

def test_checkpoint_carries_plan_fingerprint(tmp_path):
    path = str(tmp_path / "ck.bmq")
    qc = build_circuit("ghz_state", 8)
    with Simulator(qc, EngineConfig(local_bits=4)) as sim:
        plan = sim.compile()
        sim.run().save(path)
    from repro.compression.store import BlockStore
    store, meta = BlockStore.restore(path)
    store.close()
    assert meta["plan_fingerprint"] == plan.fingerprint
    # resuming with auto knobs adopts the checkpointed plan
    sim2 = Simulator.resume(path, circuit=qc, config=EngineConfig())
    try:
        assert sim2.config.local_bits == 4
    finally:
        sim2.close()


def test_resume_rejects_incompatible_plan(tmp_path):
    """A tampered/mismatched plan fingerprint in the manifest is refused
    even when every config attribute matches."""
    path = str(tmp_path / "ck.bmq")
    bad = str(tmp_path / "bad.bmq")
    qc = build_circuit("ghz_state", 8)
    with Simulator(qc, EngineConfig(local_bits=4)) as sim:
        sim.run().save(path)
    from repro.compression.store import BlockStore
    store, meta = BlockStore.restore(path)
    meta["plan_fingerprint"] = "0" * 40
    store.snapshot(bad, meta=meta)
    store.close()
    with pytest.raises(ValueError, match="incompatible execution plan"):
        Simulator.resume(bad, circuit=qc)


# -- launcher ------------------------------------------------------------------

def test_qsim_explain_prints_plan_without_executing(capsys, monkeypatch):
    from repro.core.engine import BMQSimEngine

    def boom(self, *a, **kw):
        raise AssertionError("--explain must not execute a stage")

    monkeypatch.setattr(BMQSimEngine, "run", boom)
    rc = qsim.main(["--circuit", "qft", "--qubits", "10",
                    "--memory-budget", "1", "--explain"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "ExecutionPlan" in out and "predicted" in out
    assert "[qsim] total" not in out
