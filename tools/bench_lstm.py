"""Gluon LSTM training throughput — the BASELINE.json "Gluon LSTM
tokens/sec" metric (reference analog: example/gluon/word_language_model
timed per-epoch; fused kernel src/operator/cudnn_rnn-inl.h:43 — here the
fused RNN is a lax.scan over the MXU-batched gate matmuls).

Drives the word-language-model shape through the PRODUCT path: gluon
Embedding -> LSTM -> Dense, autograd, hybridize, fused Trainer update.
tokens/sec = batch * seq_len * steps / wall.

One JSON line:
{"metric": "gluon_lstm_tokens_per_sec", "value": ..., ...}
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def measure(batch=32, seq_len=35, hidden=200, vocab=10000, layers=2,
            steps=10, ctx=None):
    import numpy as np
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, autograd

    ctx = ctx or (mx.tpu() if jax.devices()[0].platform != "cpu"
                  else mx.cpu())

    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Embedding(vocab, hidden))
    rnn = gluon.rnn.LSTM(hidden, num_layers=layers, layout="NTC")
    net.add(rnn)
    net.add(gluon.nn.Dense(vocab, flatten=False))
    net.initialize(mx.initializer.Xavier(), ctx=ctx)
    net.hybridize(static_alloc=True)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})

    rng = np.random.RandomState(0)
    data = mx.nd.array(rng.randint(0, vocab, (batch, seq_len)), ctx=ctx)
    label = mx.nd.array(rng.randint(0, vocab, (batch, seq_len)), ctx=ctx)

    def step():
        with autograd.record():
            out = net(data)
            loss = loss_fn(out, label)
        loss.backward()
        trainer.step(batch)
        return loss

    loss = step()   # warmup + compile
    jax.block_until_ready(loss._data)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step()
    jax.block_until_ready(loss._data)
    # force a real host sync
    float(np.asarray(jax.device_get(loss._data)).ravel()[0])
    dt = time.perf_counter() - t0
    toks = batch * seq_len * steps / dt
    return {
        "metric": "gluon_lstm_tokens_per_sec",
        "value": round(toks, 1),
        "unit": "tokens/s",
        "vs_baseline": None,   # reference publishes epoch times, not tok/s
        "batch": batch, "seq_len": seq_len, "hidden": hidden,
        "vocab": vocab, "layers": layers,
        "step_ms": round(dt / steps * 1e3, 2),
        "device": jax.devices()[0].device_kind,
    }


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--seq-len", type=int, default=35)
    p.add_argument("--hidden", type=int, default=200)
    p.add_argument("--vocab", type=int, default=10000)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--platform", default=None, choices=[None, "cpu"])
    args = p.parse_args()
    if args.platform == "cpu":
        import jax
        jax.config.update("jax_platforms", "cpu")
    print(json.dumps(measure(args.batch, args.seq_len, args.hidden,
                             args.vocab, args.layers, args.steps)))


if __name__ == "__main__":
    main()
