#!/usr/bin/env python3
"""Runs one cell once: loads, warms up, measures for ``--seconds``, checks
the timed path against the plain reference, prints one JSON line.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell is found by name in the manifest (``BENCHMARK.json`` at the root
of the checkout), its configuration and traffic mix in their files, and
the runner of its kind (``runners/<kind>.py``) by the configuration's
``runner``. Without a chip the run fails; ``--rehearse-cpu`` asks for a
CPU rehearsal by name, whose numbers carry ``rehearsal.`` before every
metric's name and are never a device's.
"""
import time
T_START = time.perf_counter()   # before the heavy imports: set-up counts them

import argparse          # noqa: E402
import importlib         # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import sys               # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def prepare(rehearse_cpu):
    """Before JAX is imported: the compile cache where the environment
    says, else at a fixed path inside the checkout (the path is part of
    the cache's key; the program under test follows the same rule), and
    the benchmark and the program on the path."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR") and not rehearse_cpu:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = \
            os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [BENCH_DIR, ROOT]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"),
                    help="a manifest of the shape of BENCHMARK.json "
                         "(rehearsals keep their own)")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run on the CPU, at the manifest's sizes, and "
                         "name every metric rehearsal.<name>")
    args = ap.parse_args(argv)

    prepare(args.rehearse_cpu)
    from harness import manifest
    cell = manifest.load_cell(args.manifest, ROOT, BENCH_DIR, args.workload)
    runner = importlib.import_module("runners." + cell["cfg"]["runner"])
    result = runner.run(cell, args, T_START)
    if args.rehearse_cpu:
        result["metrics"] = {"rehearsal." + k: v
                             for k, v in result["metrics"].items()}
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
