"""Where the benchmark is, for its tests (``tests/benchmarks/``, collected
with the repository's tier-1 tests; they run on the CPU at a tiny size,
and what a chip shows is in PERF.md). Importing this puts ``benchmarks/``
on the path, as ``benchmarks/run.py`` does for itself."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmarks")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)
