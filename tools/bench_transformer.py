"""GPT-style transformer LM training throughput (tokens/sec) on the
flash-attention path — the transformer counterpart of
tools/bench_lstm.py (reference analog: the word-LM benchmarks; here the
attention core is the blockwise/pallas flash kernel, so this number is
the long-context story's single-chip baseline).

Drives the PRODUCT path: the example's GPT blocks (gluon, hybridized),
autograd, fused Trainer update. tokens/sec = batch * seq_len * steps /
wall.

    python tools/bench_transformer.py [--dim 256 --layers 4 --seq 512]

One JSON line:
{"metric": "transformer_lm_tokens_per_sec", "value": ..., ...}
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "examples"))


def measure(batch=8, seq_len=512, dim=256, heads=8, layers=4,
            vocab=1024, steps=10, cpu=False):
    import jax
    if cpu:
        jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, autograd
    from train_transformer_lm import GPT, make_copy_batch

    ctx = mx.tpu() if jax.devices()[0].platform != "cpu" else mx.cpu()
    net = GPT(vocab, dim, heads, layers, seq_len)
    net.initialize(mx.initializer.Xavier(), ctx=ctx)
    net.hybridize(static_alloc=True)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 3e-4})

    rng = np.random.RandomState(0)
    data_np, label_np = make_copy_batch(rng, batch, seq_len, vocab, lag=8)
    data = mx.nd.array(data_np, ctx=ctx)
    label = mx.nd.array(label_np, ctx=ctx)

    def step():
        with autograd.record():
            out = net(data)   # pos embedding is a block Parameter
            loss = loss_fn(out, label)
        loss.backward()
        trainer.step(batch)
        return loss

    def force(l):
        # forced host fetch: cannot return before the step ran (same
        # guard as bench_lstm.py / bench.py)
        return float(np.asarray(jax.device_get(l._data)).ravel()[0])

    loss = step()   # warmup + compile
    force(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step()
    force(loss)
    dt = time.perf_counter() - t0
    tps = batch * seq_len * steps / dt
    return {
        "metric": "transformer_lm_tokens_per_sec",
        "value": round(tps, 1),
        "unit": "tokens/s",
        "vs_baseline": None,   # no reference transformer baseline exists
        "batch": batch, "seq_len": seq_len, "dim": dim,
        "layers": layers, "heads": heads,
        "step_ms": round(dt / steps * 1e3, 2),
        "device": jax.devices()[0].device_kind,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    print(json.dumps(measure(args.batch, args.seq, args.dim, args.heads,
                             args.layers, steps=args.steps, cpu=args.cpu)))


if __name__ == "__main__":
    main()
