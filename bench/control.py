#!/usr/bin/env python3
"""Readings that a cell's comparison limits are set from, on the chip.

    python3 bench/control.py --workload qaoa24.single --seed 100 \
        --program-seeds 12 --control-seeds 3

In one process (the session is built once): for each seed, the cell's
program runs the job that a run with that seed drives first in its
window, and its final state is compared with the float32 reference
(``harness.compare``).  Then the control does the same on other seeds:
the program with its own lower-precision path switched on, the lossy
codec's error bound ``b_r`` ten times looser.  For information it also
reads the float32 reference against the same reference computed in
bfloat16.  Prints one JSON line per reading and a last line with, per
number, the largest program reading and the smallest control reading.

The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(ROOT / "src"))

#: how much looser the control's error bound is than the configuration's
CONTROL_B_R_FACTOR = 10.0


def readings(cell, runner, seeds) -> list[dict]:
    import numpy as np
    from harness import _draw_job, compare
    out = []
    for seed in seeds:
        rng = np.random.default_rng(seed)
        _draw_job(cell, rng)                  # the run's warm-up job
        bindings = _draw_job(cell, rng)       # its first window job
        t0 = time.perf_counter()
        runner.job(bindings)
        job_s = time.perf_counter() - t0
        found = compare(runner, cell, bindings)
        out.append({"seed": seed, "job_s": job_s, **found})
        print(json.dumps(out[-1]), flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True,
                    help="first seed; later ones count up from it")
    ap.add_argument("--program-seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--bf16-seeds", type=int, default=3)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from harness import ControlRunner, ProgramRunner, load_benchmark, \
        load_cell
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    cell = load_cell(load_benchmark(ROOT), args.workload, ROOT)
    d0 = jax.devices()[0]
    print(f"[control] platform={d0.platform} kind={d0.device_kind}",
          file=sys.stderr, flush=True)

    seeds = iter(range(args.seed, args.seed + 10 ** 6))
    summary = {}
    b_r = cell.cfg["engine"]["b_r"] * CONTROL_B_R_FACTOR
    for kind, make, k in (
            ("program", lambda: ProgramRunner(cell), args.program_seeds),
            ("control", lambda: ProgramRunner(cell, b_r=b_r),
             args.control_seeds),
            ("bf16", lambda: ControlRunner(cell, jnp.bfloat16),
             args.bf16_seeds)):
        if k == 0:
            continue
        print(f"[control] {kind}", file=sys.stderr, flush=True)
        runner = make()
        rows = readings(cell, runner, [next(seeds) for _ in range(k)])
        runner.close()
        for key in cell.cfg["check"]:
            vals = [r[key] for r in rows]
            summary[f"{kind}.{key}"] = (max(vals) if kind == "program"
                                        else min(vals))
    print(json.dumps({"summary": summary, "control_b_r": b_r}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
