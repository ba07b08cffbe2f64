"""Single-chip ResNet-50 perf experiments: where does the step time go?

Runs the fused train step at several configurations and prints a table:
  fwd-only vs full step, batch scaling, grouped scan dispatch, optional
  XLA-flag variants (set XLA_FLAGS in the shell — it must precede jax
  init). Timing = forced host fetch after N steps (same methodology as
  bench.py).

Usage:  python tools/perf_experiments.py [--steps 20]
        [--cases fwd128,step128,step256,scan128x10]
        # fwd<N> = fwd-only batch N; step<N> = full train step;
        # scan<N>x<K> = fit(steps_per_dispatch=K): K steps per dispatch
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run(batch, steps, fwd_only=False, scan_k=0):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import models
    from mxnet_tpu.io import DataBatch, DataDesc

    dev = jax.devices()[0]
    ctx = mx.tpu() if dev.platform != "cpu" else mx.cpu()
    sym = models.resnet_symbol(num_classes=1000, num_layers=50)
    rng = np.random.RandomState(0)
    data_nd = mx.nd.array(rng.randn(batch, 3, 224, 224).astype(np.float32),
                          ctx=ctx)
    label_nd = mx.nd.array(rng.randint(0, 1000, (batch,)).astype(np.float32),
                           ctx=ctx)
    batch_obj = DataBatch(data=[data_nd], label=[label_nd])

    mod = mx.mod.Module(sym, context=ctx)

    if not fwd_only:
        # Route EVERY train case through fit() so each case reuses the ONE
        # donating jitted program bench.py measures (forward_backward would
        # compile a second, non-donating variant: a wasted compile and
        # not the benched path). scan_k<=1 -> per-step dispatch.
        scan_k = max(scan_k, 1)
        if steps % scan_k:
            # fit's grouped path only engages for FULL groups of K; an
            # undersized tail falls back to per-step and the printed number
            # would silently mix the two dispatch modes
            raise ValueError("--steps %d not divisible by scan K=%d: the "
                             "tail batches would run per-step" % (steps,
                                                                  scan_k))
        # grouped dispatch through the product API, bench.py-style timing
        class _It:
            provide_data = [DataDesc("data", (batch, 3, 224, 224))]
            provide_label = [DataDesc("softmax_label", (batch,))]
            batch_size = batch

            def __iter__(self):
                return iter([batch_obj] * steps)

            def reset(self):
                pass

        t_k = []

        def cb(epoch, symbol, a, b):
            jax.device_get(mod._exec.arg_dict[mod._param_names[0]]._data)
            t_k.append(time.perf_counter())

        mod.fit(_It(), num_epoch=3, eval_metric=None, kvstore="tpu_sync",
                optimizer="sgd",
                optimizer_params={"learning_rate": 0.05, "momentum": 0.9,
                                  "multi_precision": True},
                initializer=mx.initializer.Xavier(factor_type="in",
                                                  magnitude=2.0),
                steps_per_dispatch=scan_k, epoch_end_callback=cb)
        dt = t_k[-1] - t_k[0]
        n = steps * (len(t_k) - 1)
        return dt / n * 1e3, batch * n / dt
    mod.bind([DataDesc("data", (batch, 3, 224, 224))],
             [DataDesc("softmax_label", (batch,))],
             for_training=False)
    mod.init_params(mx.initializer.Xavier(factor_type="in", magnitude=2.0))

    def one_step():
        mod.forward(batch_obj, is_train=False)

    def force():
        arr = mod.get_outputs()[0]._data
        return float(np.asarray(jax.device_get(arr)).ravel()[0])

    one_step(); force()          # compile
    t0 = time.perf_counter()
    for _ in range(steps):
        one_step()
    force()
    dt = time.perf_counter() - t0
    return dt / steps * 1e3, batch * steps / dt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--cases", default="fwd128,step128,step256")
    args = ap.parse_args()

    for case in args.cases.split(","):
        case = case.strip()
        if case.startswith("scan"):
            b, k = (int(x) for x in case[4:].split("x"))
            ms, img_s = run(b, args.steps, scan_k=k)
            print("CASE scan(K=%-3d) b=%-4d %8.2f ms/step %10.1f img/s"
                  % (k, b, ms, img_s), flush=True)
            continue
        fwd = case.startswith("fwd")
        b = int(case.replace("fwd", "").replace("step", ""))
        ms, img_s = run(b, args.steps, fwd_only=fwd)
        kind = "fwd-only" if fwd else "train"
        print("CASE %-10s b=%-4d %8.2f ms/step %10.1f img/s"
              % (kind, b, ms, img_s), flush=True)


if __name__ == "__main__":
    main()
