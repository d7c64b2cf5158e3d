"""Spans and stage records of the stage pipeline (``repro.spans``).

Each stage leaves one :class:`StageRecord`: its spans, summed over the
dispatch thread and the pool workers, and exact counters.  These tests
check that worker time lands in the stage it belongs to, that the
dispatch thread's spans do not overlap, that ``SimStats``'s timings are
the records' sums, that the counters agree with the engine's own
ledgers, and that under a profiler session the spans are in the trace
with the durations the records hold.
"""
import importlib
import os
import sys
import threading
import time
from contextlib import contextmanager
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import EngineConfig, Simulator, build_circuit, spans
from repro.core import pipeline as pipeline_mod
from repro.core.library import random_circuit
from repro.core.pipeline import CodecBackend, StagePipeline

#: the spans the dispatch thread runs while worker pools do the host work
DISPATCH = ("pipeline.load_wait", "pipeline.dispatch",
            "pipeline.device_wait", "pipeline.d2h_wait",
            "pipeline.store_wait")


def _run(circuit, codec="device", lanes=0, local_bits=6, **cfg):
    with Simulator(circuit, EngineConfig(local_bits=local_bits,
                                         codec_backend=codec, **cfg)) as sim:
        if lanes:
            sim.run_batch([None] * lanes)
        else:
            sim.run()
        stats = sim.stats
    return stats, spans.recent(stats.last_run)


def test_span_times_into_its_record_and_none_traces_only():
    rec = spans.StageRecord(run=-5, stage=0)
    with spans.span("store.put", rec):
        time.sleep(0.01)
    with spans.span("store.put", rec):
        pass
    with spans.span("store.put", None):
        time.sleep(0.01)
    assert 0.01 <= rec.span_s["store.put"] < 0.5
    assert set(rec.span_s) == {"store.put"}


def test_records_join_the_ring_only_when_finished():
    run = spans.next_run()
    with pytest.raises(RuntimeError):
        with spans.stage_record("stage", run, 0):
            raise RuntimeError("stage failed")
    with spans.stage_record("stage", run, 1) as rec:
        pass
    assert spans.recent(run) == [rec]
    assert rec.wall_s == rec.span_s["stage"]
    assert spans.next_run() > run


def test_record_and_ring_lose_no_update_under_contention():
    """More threads than cores add to one record and finish records of
    their own while another thread reads the ring."""
    n_threads, n_iter = 2 * (os.cpu_count() or 1) + 2, 200
    rec = spans.StageRecord(run=-6, stage=0)
    run = spans.next_run()
    start = threading.Barrier(n_threads + 1)
    errors = []

    def adder():
        try:
            start.wait(10)
            for i in range(n_iter):
                rec.add_span("store.put", 1.0)
                if i % 100 == 0:
                    with spans.stage_record("stage", run, i):
                        pass
        except BaseException as e:      # pragma: no cover - failure path
            errors.append(e)

    def reader():
        start.wait(10)
        for _ in range(n_iter):
            spans.recent()

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=adder) for _ in range(n_threads)]
        threads.append(threading.Thread(target=reader))
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert not errors
    assert rec.span_s["store.put"] == float(n_threads * n_iter)
    assert len(spans.recent(run)) == n_threads * (n_iter // 100)


class _SleepyBackend(CodecBackend):
    """numpy-only backend whose store worker sleeps ``sleep_s`` a wave
    inside a ``store.put`` span, and notes the thread it ran on."""

    name = "sleepy"

    def __init__(self, n_keys):
        super().__init__(store=None, params=None, bsz=1)
        self.data = np.arange(n_keys, dtype=np.float64)
        self.sleep_s = 0.0
        self.store_threads = set()

    def fetch_group_batch(self, key_rows, rec=None):
        self.add_counts(decompressions=key_rows.size)
        return self.data[key_rows]

    def stage_to_device_batch(self, staged, device):
        return staged

    def dispatch_result_batch(self, planes_dev, n_blocks):
        return planes_dev

    def await_result_batch(self, ticket):
        return ticket

    def store_group_batch(self, key_rows, results, rec=None):
        self.store_threads.add(threading.get_ident())
        with spans.span("store.put", rec):
            time.sleep(self.sleep_s)
            self.data[key_rows] = results
        self.add_counts(compressions=key_rows.size)


def test_worker_span_lands_in_the_record_of_its_stage():
    back = _SleepyBackend(16)
    block_ids = np.arange(16).reshape(8, 2)
    recs = []
    with StagePipeline(back, depth=4, fetch_workers=1) as pipe:
        for stage, sleep_s in enumerate((0.05, 0.0)):
            back.sleep_s = sleep_s
            recs.append(pipe.run_stage(block_ids, None, [], stage=stage,
                                       wave_fn=lambda p: p + 1.0))
    assert threading.get_ident() not in back.store_threads
    first, second = recs
    assert [r.stage for r in recs] == [0, 1]
    assert first.waves == second.waves == 2
    # both waves of stage 0 slept inside its barrier, none in stage 1
    assert first.span_s["store.put"] >= 0.1
    assert second.span_s["store.put"] < 0.1
    assert first.span_s["pipeline.store"] >= first.span_s["store.put"]
    assert first.blocks_stored == second.blocks_stored == 16
    assert np.array_equal(back.data, np.arange(16) + 2.0)
    assert spans.recent(pipe.run) == recs


def test_dispatch_thread_spans_are_disjoint_and_within_the_stage(
        monkeypatch):
    intervals = []
    real = pipeline_mod.span

    @contextmanager
    def noted(name, rec):
        t0 = time.perf_counter()
        with real(name, rec):
            yield
        intervals.append((name, threading.get_ident(), t0,
                          time.perf_counter(), rec))
    monkeypatch.setattr(pipeline_mod, "span", noted)
    stats, recs = _run(build_circuit("qft", 10))
    stages = [r for r in recs if r.stage >= 0]
    assert len(stages) == stats.n_stages
    for rec in stages:
        mine = sorted((a, b) for name, tid, a, b, r in intervals
                      if r is rec and name in DISPATCH)
        assert mine
        for (_, b), (a, _) in zip(mine, mine[1:]):
            assert b <= a                       # no two overlap
        covered = sum(rec.span_s.get(k, 0.0) for k in DISPATCH)
        assert covered <= rec.wall_s
        ident = {tid for name, tid, a, b, r in intervals
                 if r is rec and name in DISPATCH}
        assert ident == {threading.get_ident()}


@pytest.mark.parametrize("codec", ["host", "device"])
def test_stats_timings_are_the_sums_of_the_record_spans(codec):
    with Simulator(build_circuit("qft", 10),
                   EngineConfig(local_bits=6, codec_backend=codec)) as sim:
        runs = []
        for _ in range(2):
            sim.run()
            runs.append(sim.stats.last_run)
        stats = sim.stats
    recs = [r for run in runs for r in spans.recent(run)]
    for field, names in (("t_decompress", ("pipeline.load",)),
                         ("t_compute", ("pipeline.dispatch",)),
                         ("t_fetch", ("pipeline.device_wait",
                                      "pipeline.d2h_wait")),
                         ("t_compress", ("pipeline.store",))):
        total = 0.0
        for r in recs:
            total += sum(r.span_s.get(name, 0.0) for name in names)
        assert getattr(stats, field) == total, field
        for name in names:
            assert sum(r.span_s.get(name, 0.0) for r in recs) > 0.0, name
    assert runs[1] > runs[0]


@pytest.mark.parametrize("codec", ["host", "device"])
def test_record_counters_match_the_engine_ledgers(codec):
    with Simulator(build_circuit("qft", 10),
                   EngineConfig(local_bits=6, codec_backend=codec)) as sim:
        sim.run()
        before = sim.stats.n_block_compressions
        loaded = sim.stats.n_block_decompressions
        sim.run()
        stats = sim.stats
        delta = stats.n_block_compressions - before
        loaded = stats.n_block_decompressions - loaded
        recs = spans.recent(stats.last_run)
        total = sim._backend.store.total_bytes
    stages = [r for r in recs if r.stage >= 0]
    assert [(r.h2d_bytes, r.d2h_bytes) for r in stages] == \
        stats.per_stage_boundary_bytes
    assert sum(r.blocks_stored for r in recs) == delta
    assert sum(r.blocks_loaded for r in recs) == loaded
    assert all(r.blocks_stored == r.blocks_loaded == 16 for r in stages)
    assert [r.stage for r in recs][0] == -1     # job start comes first
    assert "run.init" in recs[0].span_s
    assert stages[-1].stored_bytes == total
    assert all(r.lanes == 1 and r.groups * r.waves > 0 for r in stages)


@pytest.mark.parametrize("codec", ["host", "device"])
def test_random_circuit_counts_raw_escapes(codec):
    """At 16 amplitudes a block, the codec's headers outweigh what a
    random state's codes save: some blocks take the RAW escape.  A QFT's
    blocks never do."""
    _, recs = _run(random_circuit(10, 120, seed=3), codec, local_bits=4)
    raw = sum(r.raw_blocks_stored for r in recs)
    assert 0 < raw < sum(r.blocks_stored for r in recs)
    _, recs = _run(build_circuit("qft", 10), codec, local_bits=4)
    assert sum(r.raw_blocks_stored for r in recs) == 0


def test_qsim_prints_the_run_spans_and_counters(capsys):
    """``qsim``'s operator view: each span a stage and the counters of
    the run's stage records."""
    from repro.launch import qsim
    assert qsim.main(["--circuit", "qft", "--qubits", "10",
                      "--block-bits", "4", "--batch", "2"]) == 0
    out = capsys.readouterr().out.splitlines()
    stages = [r for r in spans.recent(spans.recent()[-1].run)
              if r.stage >= 0]
    assert stages
    stored = sum(r.blocks_stored for r in stages)
    assert ("[qsim] stage records: lanes a stage 2, "
            f"{sum(r.groups for r in stages)} groups in "
            f"{sum(r.waves for r in stages)} waves; blocks "
            f"{sum(r.blocks_loaded for r in stages)} loaded, {stored} "
            "stored, 0 of them RAW (0.00% incompressible); store peak in "
            f"flight {max(r.store_peak_inflight for r in stages)}") in out
    span_line = [ln for ln in out if "span seconds a stage" in ln]
    assert len(span_line) == 1
    for name in {k for r in stages for k in r.span_s}:
        assert f" {name} " in span_line[0], name


def test_batch_chunks_share_one_run_number():
    qc = build_circuit("qft", 8)
    with Simulator(qc, EngineConfig(local_bits=4)) as sim:
        budget = sim.compile().predicted.working_set_bytes
    with pytest.warns(RuntimeWarning, match="chunked"):
        stats, recs = _run(qc, "host", lanes=4, memory_budget_bytes=budget)
    assert stats.n_batch_chunks > 1
    inits = [r for r in recs if r.stage == -1]
    assert len(inits) == stats.n_batch_chunks
    assert all(r.run == stats.last_run for r in recs)
    assert sum(r.lanes for r in inits) == 4


def test_profiler_trace_holds_the_spans(tmp_path, monkeypatch):
    # the benchmark's trace reduction, importable for this test alone
    monkeypatch.syspath_prepend(Path(__file__).resolve().parents[1] / "bench")
    monkeypatch.delitem(sys.modules, "trace_reduce", raising=False)
    trace_reduce = importlib.import_module("trace_reduce")
    qc = build_circuit("qft", 10)
    with Simulator(qc, EngineConfig(local_bits=6)) as sim:
        sim.run()                               # compile outside the trace
        jax.profiler.start_trace(str(tmp_path))
        try:
            with jax.profiler.TraceAnnotation("bench.test"):
                sim.run()
        finally:
            jax.profiler.stop_trace()
        run = sim.stats.last_run
    tr = trace_reduce.load(trace_reduce.find_xplane(tmp_path),
                           min_host_ns=0)
    names = {s.name for s in tr.host}
    want = {"stage", "run.init", "pipeline.load", "pipeline.store",
            "codec.deflate", "codec.inflate", "store.get", "store.put",
            *DISPATCH}
    assert want <= names, want - names
    outer = [s for s in tr.host if s.name == "bench.test"][0]
    traced = sorted((s for s in tr.host if s.name == "stage"),
                    key=lambda s: s.start_ns)
    assert all(outer.start_ns <= s.start_ns and s.end_ns <= outer.end_ns
               for s in traced)
    stages = [r for r in spans.recent(run) if r.stage >= 0]
    assert len(traced) == len(stages)
    for s, rec in zip(traced, stages):
        assert abs(s.dur_ns / 1e9 - rec.wall_s) < 1e-3


def _lowered_text(fn, *args, **kw):
    return fn.lower(*args, **kw).as_text(debug_info=True)


def test_stage_programs_carry_the_gates_scope_under_their_module_name():
    from repro.core import engine
    plan = (((0, 1), False), ((2,), True))
    nv = 4
    planes = jnp.zeros((2, 2 ** nv), jnp.float32)
    dense = jnp.zeros((2, 4, 4), jnp.float32)
    diag = jnp.zeros((2, 2), jnp.float32)
    one = _lowered_text(engine._stage_fn(plan, nv, False, True), planes,
                        dense, diag)
    wave = _lowered_text(engine._stage_fn_wave(plan, nv, False),
                         planes[None].repeat(3, 0), dense, diag)
    batch = _lowered_text(engine._stage_fn_batch(plan, nv, False),
                          planes[None].repeat(2, 0), dense[None].repeat(2, 0),
                          diag[None].repeat(2, 0))
    for text in (one, wave, batch):
        assert "stage.gates" in text
        assert "module @jit_fn" in text


def test_codec_programs_carry_their_scopes_under_their_module_names():
    from repro.compression import device_codec as dc
    n, k = 256, 2
    enc = _lowered_text(dc._encode_group_jit,
                        jnp.zeros((2, k * n), jnp.float32), n_blocks=k,
                        step=0.01)
    codes = jnp.zeros((2 * k, n // 2), jnp.int32)
    signs = jnp.zeros((2 * k, n // 32), jnp.int32)
    l_max = jnp.zeros((2 * k, 1, 1), jnp.float32)
    dec = _lowered_text(dc._decode_blocks_jit, codes, signs, l_max, n=n,
                        step=0.01)
    assert "codec.encode" in enc and "module @jit__encode_group_jit" in enc
    assert "codec.decode" in dec and "module @jit__decode_blocks_jit" in dec
