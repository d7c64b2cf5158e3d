"""The benchmark's own tests: they run on the CPU, at sizes a test run
holds, with the harness's modules and the program importable.

    python -m pytest bench/tests
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
