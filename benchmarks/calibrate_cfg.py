#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for a
language-model cell of either kind (``train_lm_fit``, ``train_lm_cfg``: the
runner is the one the configuration names, and its ``drive`` is what is
calibrated), in one process. It is ``calibrate_lm.py`` with the runner and
the faults looked up, not named: for every seed the program's numbers against the
reference's (the lower reading), and on the first ``--control-seeds`` of
them the control (the reference itself with operands of the precision
below, which the configuration names as its ``control``, put in the
program's place) and every planted fault of the reference (its ``FAULTS``), each
against the same reference (the upper readings). One JSON line a seed, on standard output and in
``chiprun_out/calibrate_<workload>.jsonl``.

    python benchmarks/calibrate_cfg.py --workload <name> --seeds 11,12,13 \\
        --control-seeds 3 --seconds 2
"""
import argparse
import gc
import importlib
import json
import os
import resource
import sys
import time

from run import BENCH_DIR, ROOT, prepare


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--faults", default=None,
                    help="comma-separated, of the reference's FAULTS "
                         "(default: all)")
    ap.add_argument("--raw", action="store_true",
                    help="keep every leaf's gap in the line, to try other "
                         "statistics on them")
    ap.add_argument("--look", action="store_true",
                    help="on the control's seeds also the reference with "
                         "bfloat16 operands, and the share of (token, expert "
                         "layer) pairs that then choose other experts")
    ap.add_argument("--manifest",
                    default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    prepare(args.rehearse_cpu)
    import numpy as np
    from harness import compare_lm, manifest
    from runners import train_lm_fit

    cell = manifest.load_cell(args.manifest, ROOT, BENCH_DIR, args.workload)
    cfg = cell["cfg"]
    runner = importlib.import_module("runners." + cfg["runner"])
    devices = runner.devices_for(cell, args.rehearse_cpu)

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    log_path = os.path.join(ROOT, "chiprun_out",
                            "calibrate_%s.jsonl" % args.workload)
    def rss_gb():
        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6

    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        d = runner.drive(cell, seed, args.seconds, None, devices)
        ref_mod, w0, batches = d["ref"], d["w0"], d["batches"]
        faults = ref_mod.FAULTS if args.faults is None \
            else tuple(f for f in args.faults.split(",") if f)

        def reference(**kw):
            return train_lm_fit.reference_readings(ref_mod, cfg, w0, batches,
                                                   **kw)

        def against_ref(readings, leaves=False):
            out = {k: v[0] for k, v in compare_lm.numbers(
                readings, ref, w0).items()}
            if leaves:      # every leaf's gap, to try other statistics on
                out["leaves"] = compare_lm.leaf_tables(readings, ref, w0)
            return out
        ref = reference()
        row = {"workload": args.workload, "seed": seed,
               "program": against_ref(d.pop("prog"), leaves=args.raw),
               "samples_per_s": d["win"].steps * cfg["batch_size"]
               / (d["win"].t1 - d["win"].t0),
               "steps": d["win"].steps,
               "memory_peak": d["memory_peak"], "counters": d["counters"]}
        del d
        if n < args.control_seeds:
            variants = [("control", {"operand": getattr(ref_mod,
                                                        cfg["control"])})]
            variants += [(f, {"fault": f}) for f in faults]
            if args.look:
                variants.append(("reference_bf16_operands",
                                 {"operand": ref_mod.bf16_operand}))
            for name, kw in variants:
                other = reference(**kw)
                row[name] = against_ref(other, leaves=args.raw)
                if name == "reference_bf16_operands":
                    row["choice_flip_share_bf16_operands"] = float(np.mean([
                        np.mean(np.any(np.sort(other["choices1"][l], -1)
                                       != np.sort(c, -1), -1))
                        for l, c in ref["choices1"].items()]))
                del other
                gc.collect()
                print("calibrate: %s seed %d done, host peak %.1f GB"
                      % (name, seed, rss_gb()), file=sys.stderr, flush=True)
        del ref
        gc.collect()
        row["seconds"] = time.perf_counter() - t
        row["host_peak_gb"] = rss_gb()
        line = json.dumps(row)
        print(line, flush=True)
        with open(log_path, "a") as log:
            log.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
