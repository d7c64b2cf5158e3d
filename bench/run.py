#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload qft26.single --seed 7 --seconds 10 \
        --trace 0

Reads ``BENCHMARK.json`` at the root of the checkout, builds the cell
from the files it names (see ``harness.py``), and prints as the last
line of standard output one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device`` and, last, ``checks``:
each number compared with the reference beside its limit.  Without a
TPU, or with fewer chips than the cell asks for, it prints no result and
exits with 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import load_benchmark, load_cell, run_cell
    bench = load_benchmark(ROOT)
    cell = load_cell(bench, args.workload, ROOT)

    import jax
    devices = jax.devices()
    d0 = devices[0]
    print(f"[bench] platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devices)}", file=sys.stderr, flush=True)
    if d0.platform != "tpu":
        print("[bench] no TPU: this benchmark runs only on TPU chips",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"[bench] {cell.name} needs {cell.chips} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.cache import use_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    print(f"[bench] compile cache: {use_compile_cache()}", file=sys.stderr,
          flush=True)

    out = run_cell(cell, bench, args.seed, args.seconds, bool(args.trace),
                   T_START, devices)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
