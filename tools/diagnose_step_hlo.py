"""HLO-level diagnosis of the benched fused ResNet-50 train step.

VERDICT r4 weak #3: the 4x gap between the measured 57.5 ms/step and the
14.5 ms XLA-cost floor was hypothesized (dispatch latency, BN bf16<->f32
round-trips, NCHW transposes) but never evidenced. Most of the evidence
is obtainable WITHOUT the chip from the lowered StableHLO of the exact
program bench.py measures:

* `transpose` op count + total elements moved (layout shuffles);
* `convert` op count broken down by src->dst dtype pair (the BN
  bf16<->f32 statistic boundaries show up as f32<->bf16 pairs);
* convolution / dot_general counts and their element types (MXU diet).

With --on-chip it additionally compiles on the real device and reports
`memory_analysis()` (post-fusion HBM traffic), `input_output_aliases`
(donation survival on the device), and the post-optimization
TPU HLO op counts — the numbers the pre-fusion text can only bound.

    python tools/diagnose_step_hlo.py [--batch 128] [--on-chip]
    MXNET_CONV_LAYOUT=NHWC python tools/diagnose_step_hlo.py   # variant
"""
from __future__ import annotations

import argparse
import collections
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_fused(batch):
    import mxnet_tpu as mx
    from mxnet_tpu import models
    from mxnet_tpu.io import DataDesc

    sym = models.resnet_symbol(num_classes=1000, num_layers=50)
    # mx.tpu() raises without a chip unless the process is CPU-pinned
    # (JAX_PLATFORMS=cpu, the chip-free lowering analysis), which
    # context.py says once
    mod = mx.mod.Module(sym, context=mx.tpu())
    mod.bind([DataDesc("data", (batch, 3, 224, 224))],
             [DataDesc("softmax_label", (batch,))])
    mod.init_params(mx.initializer.Xavier(factor_type="in", magnitude=2.0))
    mod.init_optimizer(kvstore="tpu_sync", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9,
                                         "multi_precision": True})
    if mod._fused is None:
        raise RuntimeError("fused step did not engage")
    return mod


def lower_step(mod, donate=False):
    # met_state=None: lower the exact benched program (bench.py runs with
    # eval_metric=None, so no device-metric carry rides the step)
    ex = mod._exec
    return mod._fused.lower(ex._arg_vals(), ex._aux_vals(),
                            mod._fused_opt_state, donate=donate)


def run_sync_trace(mod, batch, steps):
    """Execute a few REAL fused fit steps with the profiler's host-sync
    tracer installed: every blocking d2h/wait prints its Python stack to
    stderr as it happens (who synced, from where), then the aggregate
    counters. An async-loop regression (a stray asnumpy in the hot path)
    shows up as d2h lines per step instead of none."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import profiler
    from mxnet_tpu.io import DataBatch

    rng = np.random.RandomState(0)
    data = mx.nd.array(rng.randn(batch, 3, 224, 224).astype(np.float32),
                       ctx=mx.context.current_context())
    label = mx.nd.array(rng.randint(0, 1000, (batch,)).astype(np.float32),
                        ctx=mx.context.current_context())
    b = DataBatch(data=[data], label=[label])
    mod._fit_step(b)  # compile outside the traced window
    profiler.reset_sync_counters()
    prev = profiler.set_sync_trace(True)
    try:
        for _ in range(steps):
            mod._fit_step(b)
        # one deliberate read — the epoch-boundary-style sync, for contrast
        print("[sync-trace] reading a parameter (expected d2h):",
              flush=True)
        mod._exec.arg_dict[mod._param_names[0]].asnumpy()
    finally:
        profiler.set_sync_trace(prev)
    print("\n== host-sync counters over %d dispatched steps ==" % steps)
    for k, v in profiler.sync_counters().items():
        print("  %-12s %s" % (k, v))


# the counters live in mxnet_tpu.hlo_stats so regression tests
# (tests/test_step_hlo_budget.py) and this CLI share one implementation
from mxnet_tpu.hlo_stats import analyze_stablehlo  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--cpu", action="store_true",
                    help="pin jax to CPU (lowering-only analysis; same "
                         "as JAX_PLATFORMS=cpu in the environment)")
    ap.add_argument("--on-chip", action="store_true",
                    help="compile on the device: memory_analysis + "
                         "donation aliases + post-opt HLO counts")
    ap.add_argument("--sync-trace", action="store_true",
                    help="run a few real fit steps with the host-sync "
                         "tracer on: every blocking d2h/wait prints a "
                         "Python stack, then the aggregate counters")
    ap.add_argument("--steps", type=int, default=4,
                    help="steps to run under --sync-trace")
    args = ap.parse_args()

    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    dev = jax.devices()[0]
    print("device: %s (%s)  batch=%d  conv_layout=%s"
          % (dev.device_kind, dev.platform, args.batch,
             os.environ.get("MXNET_CONV_LAYOUT", "NCHW")), flush=True)

    mod = build_fused(args.batch)
    if args.sync_trace:
        run_sync_trace(mod, args.batch, args.steps)
        return
    lowered = lower_step(mod)
    text = lowered.as_text()
    print("\n== pre-optimization StableHLO (exact benched program) ==")
    stats = analyze_stablehlo(text)
    for k, v in stats.items():
        print("  %-18s %s" % (k, v))

    cost = lowered.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    if cost:
        flops = float(cost.get("flops", 0))
        print("  cost flops/step    %.3f TFLOP" % (flops / 1e12))

    if not args.on_chip:
        return
    if dev.platform == "cpu":
        print("\n--on-chip requested but no accelerator present; stopping")
        return

    print("\n== compiling donating variant on %s ==" % dev.device_kind,
          flush=True)
    lowered_d = lower_step(mod, donate=True)
    compiled = lowered_d.compile()

    try:
        mem = compiled.memory_analysis()
        for f in ("temp_size_in_bytes", "argument_size_in_bytes",
                  "output_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            v = getattr(mem, f, None)
            if v is not None:
                print("  %-28s %.1f MB" % (f, v / 1e6))
    except Exception as e:  # PJRT plugins vary
        print("  memory_analysis unavailable: %s" % e)

    try:
        aliases = compiled.input_output_aliases()
        print("  input_output_aliases: %d entries" % len(aliases))
    except Exception:
        # fall back to HLO text marker
        txt = compiled.as_text()
        n = txt.count("alias")
        print("  compiled-HLO alias mentions: %d" % n)

    try:
        txt = compiled.as_text()
        post = collections.Counter(re.findall(r"^\s*\S+ = \S+? (\w+)\(",
                                              txt, re.M))
        print("  post-opt op counts (top 15):")
        for op, n in post.most_common(15):
            print("    %-22s %d" % (op, n))
        print("    transpose=%d convert=%d fusion=%d copy=%d"
              % (post.get("transpose", 0), post.get("convert", 0),
                 post.get("fusion", 0), post.get("copy", 0)))
    except Exception as e:
        print("  compiled HLO text unavailable: %s" % e)


if __name__ == "__main__":
    main()
