"""The metrics that read the program's spans (``span_time.py``), driven
on the CPU at each cell's test size as ``test_cells.py`` drives a run:
a traced run reports them, and they read the stage records of the
window's jobs alone."""
import time

import jax
import pytest

from harness import run_cell
from test_cells import CELLS, tiny

SPAN_METRICS = ("pipeline.store_wait_s_per_stage",
                "pipeline.load_wait_s_per_stage",
                "pipeline.dispatch_ms_per_stage",
                "boundary.d2h_wait_ms_per_stage")


@pytest.mark.parametrize("name", CELLS)
def test_span_metrics_read_the_window_jobs_alone(name, monkeypatch):
    """A traced run reports the span metrics; they read the stage records
    of the window's jobs, and not those of the warm-up job.  (The CPU has
    no roofline peaks: the roofline readers read nothing here.)"""
    import span_time
    import work
    from repro import spans
    monkeypatch.setattr(work, "peaks_for", lambda kind: None)
    bench, cell = tiny(name)
    seen = {r.run for r in spans.recent()}
    out = run_cell(cell, bench, 2 ** 31 + 11, 0.5, True, time.perf_counter(),
                   jax.devices())
    assert out["correct"], out["checks"]
    for m in SPAN_METRICS:
        assert out["metrics"][m]["value"] >= 0, m
    runs = sorted({r.run for r in spans.recent()} - seen)
    warm_up, window = runs[0], runs[1:]
    assert len(window) == out["attempted"] >= 1
    recs = span_time.window_records({"jobs": len(window)})
    assert {r.run for r in recs} == set(window)
    assert all(r.run != warm_up for r in recs)
    assert any(r.stage == -1 for r in recs)      # job start is in the window
