"""Benchmark driver: one module per paper table/figure (DESIGN.md §6).

    PYTHONPATH=src python -m benchmarks.run [--only NAME] [--skip-slow] \
        [--json BENCH_out.json]

Emits ``bench,key,value`` CSV on stdout; ``--json`` additionally writes a
machine-readable dump (per-bench rows + wall time) so the perf trajectory
— stage-compute times, boundary bytes, transpose counts — diffs cleanly
across PRs.  EXPERIMENTS.md archives a run.
"""
import argparse
import json
import sys
import time

from . import (bench_fidelity, bench_max_qubits, bench_memory,
               bench_multidev, bench_overhead, bench_partition,
               bench_pipeline, bench_sc19, bench_serve, bench_session,
               bench_sim_time, bench_tuning)
from .common import drain_rows
from repro.launch.cache import use_compile_cache

BENCHES = {
    "max_qubits": bench_max_qubits.main,     # Table 2
    "sc19": bench_sc19.main,                 # Fig. 7
    "fidelity": bench_fidelity.main,         # Fig. 8
    "memory": bench_memory.main,             # Fig. 9
    "sim_time": bench_sim_time.main,         # Fig. 10
    "overhead": bench_overhead.main,         # Fig. 11
    "pipeline": bench_pipeline.main,         # Fig. 12 + stage compute
    "multidev": bench_multidev.main,         # Fig. 13
    "partition": bench_partition.main,       # Fig. 14
    "tuning": bench_tuning.main,             # Fig. 15
    "session": bench_session.main,           # Simulator API reuse/readout
    "serve": bench_serve.main,               # service tier cold/warm + merge
}
SLOW = {"multidev"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--skip-slow", action="store_true")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write a machine-readable JSON dump "
                         "(convention: BENCH_<date>.json)")
    args = ap.parse_args(argv)
    use_compile_cache()
    names = [args.only] if args.only else list(BENCHES)
    print("bench,key,value")
    report: dict = {"benches": {}, "unix_time": time.time()}
    drain_rows()                     # discard rows from stray imports
    for name in names:
        if args.skip_slow and name in SLOW:
            continue
        t0 = time.time()
        BENCHES[name]()
        elapsed = time.time() - t0
        print(f"{name},elapsed_s,{elapsed:.1f}", flush=True)
        entry: dict = {"elapsed_s": elapsed, "metrics": {}}
        for bench, key, value in drain_rows():
            entry["metrics"].setdefault(bench, {})[key] = value
        report["benches"][name] = entry
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(report, fh, indent=2, sort_keys=True)
        print(f"# wrote {args.json}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
