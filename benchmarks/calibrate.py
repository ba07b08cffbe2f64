#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, in one process:
for every seed the program's numbers against the reference's (the lower
reading), and on the first ``--control-seeds`` of them the control (the
reference itself with operands of the precision below, which the
configuration names as its ``control``, put in the program's place)
and the planted faults (half of the batch left out; on several chips, the
exchange left out, which leaves each chip its own rows), each against the
same reference (the upper readings). A state left unchanged reads 1 by
the measure and needs no run. One JSON line a seed, on standard output
and in ``chiprun_out/calibrate_<workload>.jsonl``.

    python benchmarks/calibrate.py --workload <name> --seeds 11,12,13 \\
        --control-seeds 3 --seconds 2
"""
import argparse
import json
import os
import sys
import time

from run import BENCH_DIR, ROOT, prepare


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--raw", action="store_true",
                    help="keep every leaf's norm in the line, to try other "
                         "statistics on them")
    ap.add_argument("--look", action="store_true",
                    help="on the control's seeds also: the reference with "
                         "bfloat16 operands, and the program itself with "
                         "multi_precision off (float32 storage)")
    ap.add_argument("--manifest",
                    default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    prepare(args.rehearse_cpu)
    from harness import compare, manifest
    from runners import train_fit

    cell = manifest.load_cell(args.manifest, ROOT, BENCH_DIR, args.workload)
    cfg = cell["cfg"]
    devices = train_fit.devices_for(cell, args.rehearse_cpu)

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    log_path = os.path.join(ROOT, "chiprun_out",
                            "calibrate_%s.jsonl" % args.workload)
    batch = cfg["batch_size"]
    faults = {"half_batch": slice(0, batch // 2)}
    if cell["chips"] > 1:
        faults["no_exchange"] = slice(0, batch // cell["chips"])
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        d = train_fit.drive(cell, seed, args.seconds, None, devices)

        def against_ref(readings):
            return {k: v[0] for k, v in
                    compare.numbers(readings, ref).items()}

        def reference(**kw):
            return train_fit.reference_readings(
                d["ref"], cfg, d["w0"], d["aux0"], d["batches"], **kw)
        ref = reference()
        row = {"workload": args.workload, "seed": seed,
               "program": against_ref(d["prog"]),
               "img_per_s": d["win"].steps * batch
               / (d["win"].t1 - d["win"].t0),
               "memory_peak": d["memory_peak"]}
        raw = {"reference": ref, "program": d["prog"]}
        if n < args.control_seeds:
            raw["control"] = reference(
                operand=getattr(d["ref"], cfg["control"]))
            for name, rows in faults.items():
                raw[name] = reference(rows=rows)
            if args.look:
                raw["reference_bf16_operands"] = reference(
                    operand=d["ref"].bf16_operand)
                f32 = dict(cell, cfg=dict(cfg, optimizer=dict(
                    cfg["optimizer"], multi_precision=False)))
                raw["program_f32"] = train_fit.drive(
                    f32, seed, args.seconds, None, devices)["prog"]
            for name, readings in raw.items():
                if name not in ("reference", "program"):
                    row[name] = against_ref(readings)
        if args.raw:
            row["raw"] = raw
        row["seconds"] = time.perf_counter() - t
        line = json.dumps(row)
        print(line, flush=True)
        with open(log_path, "a") as log:
            log.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
